"""Host seconds the image feed spent per batch on its slab view and upload
(the growth of ``ImageFeed.host_prep_s`` over the window), in ms."""


def read(run, reduced, peaks):
    n = run.counters.get("batches")
    if not n or "host_prep_s" not in run.counters:
        return None
    return 1e3 * run.counters["host_prep_s"] / n
