"""idle_pct.wire: the share of the traced window in which no operation ran
on the device, in %, averaged over the chips the cell uses."""

from benchmark.tracefile import idle_pct


def read(view):
    return None if view.trace is None else idle_pct(view.trace)
