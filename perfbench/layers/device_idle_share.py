"""device_idle_share (%): 1 - (union of the kernel intervals on a chip)
/ (the chip's span from its first kernel to its last), on the chip's own
clock, averaged over the chips; profiler trace."""


def read(run):
    share = run.summary.idle_share()
    return None if share is None else 100.0 * share
