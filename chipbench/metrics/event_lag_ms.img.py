"""Mean lateness of the simulator's events, in ms: the growth of the
recorder's ``clock.lag_s`` (fire time less due time) over that of
``clock.events``."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.event_lag_ms(run)
