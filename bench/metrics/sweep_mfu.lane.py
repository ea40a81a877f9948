"""The whole deposit + sweep step's share of the chip's peak over the
traced window, in percent: the least time the chip needs for the useful
work of the window (the ready μVM tiles and the poll bytes of the
deposited frames, counted as in ``uvm_roofline.lane`` and
``poll_roofline.lane``), against the bound that applies (memory), over the
window's length.  It bounds any gain, whichever kernel does the work."""

from bench.flops import poll_bytes, uvm_tiles
from bench.harness import roofline_s


def read(r):
    _, sweeps = r.trace.program_time(("jit_sweep",))
    tiles = r.counts.get("resolved", 0) * r.records["n_tiles"]
    if not tiles or not r.trace.window_s:
        return None
    fl, by = uvm_tiles(tiles, r.records["tile"], sweeps)
    by += poll_bytes(r.counts.get("frames", 0), r.records["agg_k"])
    return roofline_s(fl, by, r.peak)[0] / r.trace.window_s * 100
