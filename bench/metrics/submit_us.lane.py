"""Host microseconds per invocation inside the benchmark's span around
``TaskRuntime.submit`` / ``submit_many`` (task runtime + dispatcher)."""


def read(r):
    s, n, _ = r.spans.total("bench.submit")
    return s / n * 1e6 if n else None
