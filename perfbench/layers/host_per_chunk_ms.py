"""host_per_chunk_ms (ms): mean host time of the benchmark's ``feed``
span around each fold call of the streaming fleet (host-to-device
transfer of one chunk of rates plus the enqueue of its fold)."""


def read(run):
    feeds = run.spans.durations("feed")
    if not feeds:
        return None
    return 1e3 * sum(feeds) / len(feeds)
