"""The engine's own host time per step: ``serve.step`` less the blocking
token read-back (``serve.read_back.wait``), from the program's phase
clock (``ServingEngine.phases.recent``, perf_counter), over the steps
that ended in the window outside its profiled stretch.  A program
without the phase clock reads nothing."""

from scopes import host_ms_per_step


def read(run):
    phases = getattr(getattr(run.probe, "eng", None), "phases", None)
    recent = getattr(phases, "recent", None)
    if not recent:
        return None
    win = run.window
    return host_ms_per_step(recent, (win.p0, win.p0 + win.seconds),
                            win.trace_p)
