"""Device milliseconds per run of the jitted deposit program (the
``ppermute`` of the staged generation into the ring), from the trace."""


def read(r):
    t, n = r.trace.program_time(("jit_deposit",))
    return t / n * 1e3 if n else None
