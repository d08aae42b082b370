"""fetch_ms.mesh: host milliseconds per mesh step spent copying the
step's digests, version vector and member count to the host and widening
them to u64 (the program's ``mesh.step.fetch`` spans, one per step, on
the trace's clock)."""

from benchmark import spans


def read(view):
    return spans.mean_ms(view.trace, spans.named("mesh.step.fetch"))
