#!/usr/bin/env python3
"""The control: the reference with one stated guarantee broken, put in
the program's place.  A sound check reads the control as not correct.

Joins: buffered removes are never replayed (``merge(..., replay=False)``),
the step a faster join would be tempted to skip.  Reads: ``contains``
answers the set clock as its remove context instead of the member's
dots, skipping the per-member gather.

Run at a cell's own size on the chip, one process per seed:

    python3 benchmark/tests/control.py --workload fold.star --seeds 1,2,3

Each seed's run is an ordinary run whose check compares the program
with the control instead of the reference: with a sound program, each
number compared is then the control's reading.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@contextlib.contextmanager
def control():
    from benchmark import reference

    merge, read = reference.merge, reference.read

    def no_replay(x, y, replay=True):
        return merge(x, y, replay=False)

    def set_clock_rm(state, member, width):
        val, add, rm = read(state, member, width)
        return (val, add, add.copy()) if member >= 0 else (val, add, rm)

    reference.merge, reference.read = no_replay, set_clock_rm
    try:
        yield
    finally:
        reference.merge, reference.read = merge, read


def main(argv=None) -> None:
    from benchmark import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = run.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        with control():
            out = run.run_cell(cell, seed, args.seconds, False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": out["checks"]}), flush=True)


if __name__ == "__main__":
    sys.path[0] = ROOT
    main()
    sys.stdout.flush()
    os._exit(0)
