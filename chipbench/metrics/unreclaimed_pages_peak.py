"""The most pages retired and not yet reclaimed (pool.unreclaimed(),
sampled after every engine step of the window): the paper's quantity."""


def read(run):
    if run.probe is None or not run.probe.unreclaimed:
        return None
    return float(max(run.probe.unreclaimed))
