"""Device milliseconds of the Mamba-2 state-step kernel
(``kernels/ssd_step.py``, one call per Mamba layer) per run of the jitted
decode step, over the traced window; nothing where the trace holds no
such kernel (a program without it)."""

import re

# the Mosaic custom call of ``ssd_state_step``, found by the kernel's name
KERNEL = re.compile(r"^%ssd_step[.\d]* = .*tpu_custom_call", re.S)


def read(r):
    t, _ = r.trace.op_time(KERNEL.match)
    _, steps = r.trace.program_time(("jit_decode_step",))
    if not t or not steps:
        return None
    return t / steps * 1e3
