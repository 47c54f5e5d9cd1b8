"""Share of the traced window in which no op ran on the chip, in %."""


def read(run, reduced, peaks):
    if reduced is None:
        return None
    return 100.0 * reduced.idle_share
