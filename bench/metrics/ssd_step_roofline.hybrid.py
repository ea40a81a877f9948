"""The Mamba-2 state-step kernel's share of its roofline, in percent: the
least time the chip needs for the useful work of every token decoded in the
window in every Mamba layer (``ssd_step_work`` of the configuration's
reference: each sequence's float32 state read and written once, about 0.75
FLOP per byte, so the memory bound applies) over the kernel's device time.
Slots without a sequence are not counted as work."""

from bench.harness import load_module, roofline_s

KERNEL = load_module("metrics", "ssd_step_ms.hybrid").KERNEL


def read(r):
    t, _ = r.trace.op_time(KERNEL.match)
    tokens = r.counts.get("tick_tokens", 0)
    if not t or not tokens:
        return None
    ref = load_module("reference", r.config["reference"])
    d = ref.Dims.of(r.config)
    fl, by = ref.ssd_step_work(d, tokens * len(d.index("mamba")))
    return roofline_s(fl, by, r.peak)[0] / t * 100
