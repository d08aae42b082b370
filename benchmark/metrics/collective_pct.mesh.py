"""collective_pct.mesh: the share of the mesh step's device time spent in
collective operations (all-gather, all-reduce and the like, by their HLO
op names), in %, averaged over the chips."""

from benchmark.tracefile import COLLECTIVE

STEP = r"^jit__step"


def read(view):
    trace = view.trace
    if trace is None:
        return None
    step = trace.program_s(STEP)
    coll = trace.op_s(COLLECTIVE)
    shares = [100.0 * c / s for c, s in zip(coll, step) if s > 0]
    return sum(shares) / len(shares) if shares else None
