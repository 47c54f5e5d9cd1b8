"""The part of each window batch's ``feed.kernel_wait``, placed on the
device's timeline, in which no op ran on the chip, in ms a batch: the wait
for the 100 MB upload, with the crop program's own device time taken out."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.upload_ms(run, reduced)
