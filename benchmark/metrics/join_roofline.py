"""join_roofline: the join program's share of the HBM roofline, in %.

Bytes: ``work.join_bytes`` per round (every input replica read once,
the fixpoint written once, whatever merge implementation ran) times the
program's executions in the traced window.  Time: the device time of
those executions, on the slowest chip.  The least time is bytes over
the chips' summed HBM bandwidth (``peaks.json``); the merge does no
matrix product, so bandwidth bounds it.
"""

from benchmark import work

#: the join program of each loop, by its XLA module name
PROGRAMS = {"fold_rounds": r"^jit__fold_tree", "mesh_steps": r"^jit__step"}


def read(view):
    trace = view.trace
    pattern = PROGRAMS.get(view.traffic["driver"])
    if trace is None or pattern is None:
        return None
    seconds = trace.program_s(pattern)
    calls = trace.program_calls(pattern)
    if not seconds or max(calls) == 0:
        return None
    slowest = max(range(len(seconds)), key=lambda i: seconds[i])
    nbytes = view.stats["join_bytes"] * calls[slowest]
    return work.roofline_pct(nbytes, seconds[slowest], view.device_kind,
                             chips=view.chips)
