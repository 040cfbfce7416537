"""collective_share (%): device time in collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) over device
busy time, averaged over the chips; profiler trace."""


def read(run):
    s = run.summary
    if len(s.devices) < 2 or s.busy_s <= 0:
        return None
    return 100.0 * s.collective_s() / s.busy_s
