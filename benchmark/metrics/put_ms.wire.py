"""put_ms.wire: host milliseconds per round spent copying the round's
staging planes to the device, until each copy completes (the program's
``wireloop.put`` spans, on the trace's clock)."""

from benchmark import spans


def read(view):
    return spans.ms_per(view.trace, spans.named("wireloop.put"),
                        view.stats.get("rounds", 0))
