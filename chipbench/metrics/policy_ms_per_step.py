"""The reclamation policy's host time (BlockPool.begin_step and
complete_step, timed by the benchmark on the instance) per engine step
of the window, outside the profiled stretch of a traced run."""


def read(run):
    steps = run.window.untraced[1]
    if run.probe is None or not steps:
        return None
    return 1e3 * run.probe.policy_s / steps
