"""gather_ms.reads: device milliseconds per read frame spent in the
serve path's programs (the gather and the read-heat updates), from the
trace's program executions in the window."""

#: every program the serve path runs per frame
PROGRAMS = r"^jit_"


def read(view):
    trace = view.trace
    frames = view.stats.get("frames", 0)
    if trace is None or not frames:
        return None
    seconds = trace.program_s(PROGRAMS)
    if not seconds or max(seconds) == 0:
        return None
    return 1000.0 * seconds[0] / frames
