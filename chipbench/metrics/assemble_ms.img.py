"""Mean time the loader's event thread spent copying one batch into its
arena slab, in ms a window batch: the ``loader.assemble`` spans of the
program's span recorder."""

from chipbench import spans


def read(run, reduced, peaks):
    return spans.per_batch_ms(run, "loader.assemble")
