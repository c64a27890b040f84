"""Share of the traced window in which no operation ran on the chip."""


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share
