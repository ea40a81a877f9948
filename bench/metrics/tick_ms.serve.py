"""Host milliseconds per ``Server.tick`` (one jitted decode step over all
slots and the host's token bookkeeping), inside the benchmark's span."""


def read(r):
    s, _, c = r.spans.total("bench.tick")
    return s / c * 1e3 if c else None
