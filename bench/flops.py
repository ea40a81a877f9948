"""Operations and bytes of the work each cell asks for, from its shapes.

The rooflines and the whole-step utilizations count useful work only:
what the request needs, not what the program happens to compute (masked
slots, padding, a cache read past a sequence's end).
"""

from __future__ import annotations

F32 = 4
WORD = 4
HDR_WORDS = 5            # device word-frame header (kernels/ring_poll.py)


def uvm_tiles(tiles: int, tile: int, sweeps: int) -> tuple[float, float]:
    """(FLOP, bytes) of ``uvm_affine`` over ``tiles`` ready tiles in
    ``sweeps`` sweeps: one tile x W matmul (2 T^3) each; each tile read and
    written once (2 T^2 f32), W read once per sweep."""
    return (2.0 * tile ** 3 * tiles,
            2.0 * tile * tile * F32 * tiles + tile * tile * F32 * sweeps)


def poll_bytes(frames: int, agg_k: int) -> float:
    """Bytes the ring poll must read and write per deposited frame: the
    header, the ``agg_k`` descriptor pairs of a container, the trailer
    word, and one status word (plus ``agg_k`` sub-statuses)."""
    words = HDR_WORDS + 2 * agg_k + 1 + 1 + agg_k
    return float(frames * words * WORD)


def llama_linear_params(d) -> tuple[float, float]:
    """(parameters in the layers' matmuls, parameters of the LM head)."""
    H, K, hd, D, F = d.heads, d.kv_heads, d.head_dim, d.d_model, d.d_ff
    per_layer = D * (H + 2 * K) * hd + H * hd * D + 3 * D * F
    return float(d.layers * per_layer), float(D * d.vocab)


def llama_serve_flops(d, *, prefill_tokens: float, prefill_pairs: float,
                      prompts: float, decode_tokens: float,
                      decode_ctx: float) -> float:
    """Model FLOPs of serving: every prompt token through the layers, the
    LM head once per prompt, causal attention over ``prefill_pairs``
    (query, key) pairs; every decoded token through layers and head, with
    attention over ``decode_ctx`` keys in all."""
    lin, head = llama_linear_params(d)
    attn = 4.0 * d.layers * d.heads * d.head_dim       # per (query, key) pair
    return (2 * lin * (prefill_tokens + decode_tokens)
            + 2 * head * (prompts + decode_tokens)
            + attn * (prefill_pairs + decode_ctx))
