"""Host milliseconds per ``Server.admit`` (prefill, first token, splice
into the batcher's cache), inside the benchmark's span."""


def read(r):
    s, _, c = r.spans.total("bench.admit")
    return s / c * 1e3 if c else None
