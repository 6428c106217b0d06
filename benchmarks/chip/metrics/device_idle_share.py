"""Device: share of the traced window in which no operation ran on the
chip, in percent (one minus the union of the operations' intervals)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_ns"] / run.trace["window_ns"])
