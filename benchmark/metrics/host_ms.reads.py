"""host_ms.reads: host milliseconds per read frame spent in the serve
path's host legs (decode, admit, dispatch, fetch, heat, encode: every
``serve.leg.*`` span but ``serve.leg.wait``, the gather's device time),
summed over the clients' threads, on the trace's clock."""

from benchmark import spans


def _host_leg(name: str) -> bool:
    return name.startswith("serve.leg.") and name != "serve.leg.wait"


def read(view):
    return spans.ms_per(view.trace, _host_leg, view.stats.get("frames", 0))
