"""fallback_pct.named: the share of the window's wire blobs, in and out,
that the program counted under ``wire.orswot.*.fallback`` (the Python
codec), in %; from the driver's counter deltas over the window.  None
where the driver does not count them."""


def read(view):
    blobs = view.stats.get("wire_blobs")
    if not blobs:
        return None
    return 100.0 * view.stats["wire_fallback"] / blobs
