"""Mean seconds each train step waited on the loader, in ms: the
``step_stats.wait_s`` that ``run_training`` returns, for the window's
steps, on the loader's clock (the wall clock, with
``virtual_clock=False``)."""


def read(run, reduced, peaks):
    waits = run.counters.get("wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
