"""Mean time the image feed waited on its batch's upload and crop kernel,
in ms a window batch: the ``feed.kernel_wait`` spans (``block_until_ready``
on the kernel's output) of the program's span recorder."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.per_batch_ms(run, "feed.kernel_wait")
