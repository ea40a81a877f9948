"""Host microseconds per request ingested, inside the benchmark's spans
around ``IfuncFrontend.submit`` and ``IfuncFrontend.server_poll`` (the
ifunc front end over ``TaskRuntime`` / ``Dispatcher``)."""


def read(r):
    s, n, _ = r.spans.total("bench.ingest")
    p, _, _ = r.spans.total("bench.server_poll")
    return (s + p) / n * 1e6 if n else None
