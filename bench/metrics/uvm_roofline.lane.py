"""The ``ifunc_vm`` kernel's share of its roofline, in percent: the least
time the chip needs for the ready tiles' work (2 T^3 FLOP per tile; its
64 KiB in and out, W once per sweep) over the kernel's device time.  At
32 FLOP per byte, under the v5e's ridge of 240, the memory bound applies."""

import re

from bench.flops import uvm_tiles
from bench.harness import roofline_s

# the Mosaic custom call that returns the f32 output tiles of ``ifunc_vm``
KERNEL = re.compile(r"^%[\w.-]+ = f32\[.*tpu_custom_call", re.S)


def read(r):
    t, _ = r.trace.op_time(KERNEL.match)
    _, sweeps = r.trace.program_time(("jit_sweep",))
    tiles = r.counts.get("resolved", 0) * r.records["n_tiles"]
    if not t or not tiles:
        return None
    fl, by = uvm_tiles(tiles, r.records["tile"], sweeps)
    return roofline_s(fl, by, r.peak)[0] / t * 100
