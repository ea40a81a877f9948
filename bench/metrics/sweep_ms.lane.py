"""Device milliseconds per run of the jitted sweep program (ring poll,
μVM, clear), from the trace."""


def read(r):
    t, n = r.trace.program_time(("jit_sweep",))
    return t / n * 1e3 if n else None
