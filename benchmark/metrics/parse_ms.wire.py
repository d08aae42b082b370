"""parse_ms.wire: host milliseconds per round spent parsing wire blobs
into staging planes, from ``PipelinedWireLoop.run``'s ``stage_s["parse"]``
(wall time on the parser thread; with overlap it runs beside the fold)."""


def read(view):
    rounds = view.stats.get("rounds", 0)
    if not rounds:
        return None
    return 1000.0 * view.stats["stage_s"]["parse"] / rounds
