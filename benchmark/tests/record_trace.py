#!/usr/bin/env python3
"""Record the small profiler trace that ``test_tracefile.py`` reduces.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

On the chip: two joins of 2 replicas x 2,048 objects at the
north-star width inside a ``bench.window`` annotation, each in a
``bench.round`` span.  Writes the trace's ``.xplane.pb`` to the given
path and prints what the test expects of it.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> None:
    import jax

    import crdt_tpu  # noqa: F401
    from benchmark import gen
    from benchmark.tracefile import find_xplane, reduce_xplane
    from crdt_tpu.batch import OrswotBatch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "configs",
            "orswot-star-1chip.json")) as f:
        cfg = json.load(f)
    planes = gen.fleet_builder(cfg, n=2048, r=2)(gen.device_key(1, 1))
    fleets = [OrswotBatch(*p) for p in planes]
    jax.block_until_ready(OrswotBatch.join_fleet(fleets))
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in range(2):
            with jax.profiler.TraceAnnotation("bench.round"):
                jax.block_until_ready(OrswotBatch.join_fleet(
                    fleets[k:] + fleets[:k]))
    jax.profiler.stop_trace()
    path = find_xplane(log_dir)
    shutil.copyfile(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    t = reduce_xplane(out, devices=1)
    print(json.dumps({
        "bytes": os.path.getsize(out), "window_s": t.window_s,
        "busy_s": t.busy_s(), "devices": [d.name for d in t.devices],
        "fold_calls": t.program_calls(r"^jit__fold_tree"),
        "fold_s": t.program_s(r"^jit__fold_tree"),
        "modules": sorted({e.name for d in t.devices for e in d.modules}),
        "top_ops": t.top_ops(5), "idle_gaps": t.idle_gaps(3)}))


if __name__ == "__main__":
    main(sys.argv[1])
