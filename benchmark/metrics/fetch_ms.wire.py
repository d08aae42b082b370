"""fetch_ms.wire: host milliseconds per round spent copying the round's
fixpoint planes from the device to the host, after the fold has finished
(the program's ``wireloop.fetch`` spans, on the trace's clock)."""

from benchmark import spans


def read(view):
    return spans.ms_per(view.trace, spans.named("wireloop.fetch"),
                        view.stats.get("rounds", 0))
