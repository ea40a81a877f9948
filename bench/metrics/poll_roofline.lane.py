"""The ring-poll kernels' (``ring_poll`` / ``agg_ring_poll``) share of
their roofline, in percent: the header, descriptor and trailer bytes of
the frames deposited in the traced window, and their status words, at the
chip's memory bandwidth (no arithmetic: the memory bound applies), over
the kernels' device time."""

import re

from bench.flops import poll_bytes
from bench.harness import roofline_s

# the Mosaic custom calls that return the int32 slot (and sub-record)
# statuses of ``ring_poll`` / ``agg_ring_poll``
KERNEL = re.compile(r"^%[\w.-]+ = \(?s32\[.*tpu_custom_call", re.S)


def read(r):
    t, _ = r.trace.op_time(KERNEL.match)
    frames = r.counts.get("frames", 0)
    if not t or not frames:
        return None
    by = poll_bytes(frames, r.records["agg_k"])
    return roofline_s(0.0, by, r.peak)[0] / t * 100
