"""Share of the window the simulator's event thread spent inside its
events, in %: the growth of the recorder's ``clock.busy_s`` over the
window's host time."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.event_busy(run)
