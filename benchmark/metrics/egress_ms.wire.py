"""egress_ms.wire: host milliseconds per round from the end of the fold's
dispatch to the round's blobs out, from ``PipelinedWireLoop.run``'s
``stage_s["egress"]``: the wait for the device, the copy to the host and
the encode."""


def read(view):
    rounds = view.stats.get("rounds", 0)
    if not rounds:
        return None
    return 1000.0 * view.stats["stage_s"]["egress"] / rounds
