"""Engine steps: the window's length over the steps taken in it, both
outside the profiled stretch of a traced run (host clock)."""


def read(run):
    seconds, steps = run.window.untraced
    if not steps:
        return None
    return 1e3 * seconds / steps
