"""scan_iter_us (us): device time of the timed program (its XLA module
events in the trace, averaged over the chips) per serial minute-iteration
of the lane core it ran in the traced window."""


def read(run):
    t = run.summary.module_s(run.driver.module)
    n = run.driver.iterations * run.dispatches
    if t <= 0 or n <= 0:
        return None
    return 1e6 * t / n
