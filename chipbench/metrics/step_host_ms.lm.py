"""Host time between train steps, in ms: the mean time from the end of one
window step's ``train.step`` span (dispatch through ``block_until_ready``)
to the start of the next."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.step_host_ms(run)
