"""Model FLOP utilization of serving over the traced window, in percent:
the model FLOPs of every prompt token prefilled and every token decoded in
the window, at their contexts (the configuration's reference counts them
from its shapes), over the window's length times the chip's bf16 peak."""

from bench.harness import load_module


def read(r):
    c, w = r.counts, r.trace.window_s
    if not w or not (c.get("prefill_tokens") or c.get("tick_tokens")):
        return None
    ref = load_module("reference", r.config["reference"])
    fl = ref.serve_flops(
        ref.Dims.of(r.config), prefill_tokens=c["prefill_tokens"],
        prefill_pairs=c["prefill_pairs"], prompts=c["admitted"],
        decode_tokens=c["tick_tokens"], decode_ctx=c["ctx_tokens"])
    return fl / (w * r.peak["bf16_flops"]) * 100
