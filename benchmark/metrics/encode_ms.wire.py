"""encode_ms.wire: host milliseconds per round spent encoding the
round's fixpoint as wire blobs (the program's ``wireloop.encode`` spans,
on the trace's clock)."""

from benchmark import spans


def read(view):
    return spans.ms_per(view.trace, spans.named("wireloop.encode"),
                        view.stats.get("rounds", 0))
