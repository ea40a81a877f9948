"""Host microseconds per completed invocation inside the benchmark's span
around ``TaskRuntime.progress`` (flush, the device lane's sweep with its
readback, reply demux)."""


def read(r):
    s, _, _ = r.spans.total("bench.progress")
    n = r.counts.get("resolved", 0)
    return s / n * 1e6 if n else None
