"""Mean seconds each ``next()`` of the image feed waited on the loader, in
ms: the feed's ``step_stats.wait_s`` over the window, on the loader's
clock (the wall clock, with ``virtual_clock=False``)."""


def read(run, reduced, peaks):
    waits = run.counters.get("wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
