"""Core layers: RMSNorm, RoPE, GQA attention (chunked train / cached decode), MLP.

Parameter conventions
---------------------
Every module exposes ``<mod>_specs(cfg, ...) -> dict[name, (shape, logical_axes)]``
and a shared generic initializer consumes those specs.  Attention weights are
kept 3-D ``[d_model, heads, head_dim]`` so TP shards whole heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import (current_mesh, logical_sharding, shard_act,
                                     shard_map)

# ---------------------------------------------------------------------------
# generic param plumbing

Spec = tuple[tuple[int, ...], tuple[str | None, ...]]


def init_from_specs(specs: dict[str, Spec], key, dtype) -> dict:
    params = {}
    keys = jax.random.split(key, len(specs))
    for k, (name, (shape, _axes)) in zip(keys, sorted(specs.items())):
        if name.endswith("_scale") or name.endswith("norm"):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("_bias") or name.endswith("_b"):
            params[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
            std = min(0.02, 1.0 / np.sqrt(fan_in))
            params[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    return params


def specs_shapes(specs: dict[str, Spec], dtype) -> dict:
    return {n: jax.ShapeDtypeStruct(s, dtype) for n, (s, _) in specs.items()}


def specs_axes(specs: dict[str, Spec]) -> dict:
    return {n: a for n, (_, a) in specs.items()}


# ---------------------------------------------------------------------------
# norm


def rmsnorm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def norm_specs(prefix: str, d: int) -> dict[str, Spec]:
    return {f"{prefix}_scale": ((d,), ("norm",))}


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., seq, heads, head_dim]; positions: [..., seq] int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))  # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention


def attn_specs(cfg) -> dict[str, Spec]:
    D, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s: dict[str, Spec] = {
        "wq": ((D, H, hd), ("embed", "heads", None)),
        "wk": ((D, Kv, hd), ("embed", "kv_heads", None)),
        "wv": ((D, Kv, hd), ("embed", "kv_heads", None)),
        "wo": ((H, hd, D), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        s["wq_b"] = ((H, hd), ("heads", None))
        s["wk_b"] = ((Kv, hd), ("kv_heads", None))
        s["wv_b"] = ((Kv, hd), ("kv_heads", None))
    return s


def _softcap(scores, cap: float):
    if cap and cap > 0:
        return jnp.tanh(scores / cap) * cap
    return scores


def _qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"], preferred_element_type=x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"], preferred_element_type=x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"], preferred_element_type=x.dtype)
    if cfg.qkv_bias:
        q, k, v = q + p["wq_b"], k + p["wk_b"], v + p["wv_b"]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_seq_kv(p, x, cfg, *, window: int = 0):
    """Full-sequence (train / prefill) attention.

    x: [B,S,D] -> ([B,S,D], (k_kv, v_kv)) where k_kv/v_kv are the rope'd
    pre-repeat KV tensors [B,S,Kv,hd] (for cache construction).

    Q is processed in ``cfg.q_chunk`` blocks via lax.scan, bounding the live
    score tensor to [B, H, q_chunk, S].  KV is repeated to the full head
    count so the head axis shards evenly over TP.
    """
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    k_kv, v_kv = k, v
    if cfg.group_size > 1:
        k = jnp.repeat(k, cfg.group_size, axis=2)
        v = jnp.repeat(v, cfg.group_size, axis=2)
    q = shard_act(q, "batch", "seq", "act_heads", None)
    k = shard_act(k, "batch", "seq", "act_heads", None)
    v = shard_act(v, "batch", "seq", "act_heads", None)
    scale = cfg.attention_multiplier or 1.0 / np.sqrt(hd)
    kpos = jnp.arange(S, dtype=jnp.int32)

    def block_naive(qc, qpos0):
        qpos = qpos0 + jnp.arange(qc.shape[1], dtype=jnp.int32)
        s_ = jnp.einsum("bqhk,bthk->bhqt", qc, k, preferred_element_type=jnp.float32)
        s_ = _softcap(s_ * scale, cfg.attn_logit_softcap)
        m = qpos[:, None] >= kpos[None, :]
        if window:
            m &= qpos[:, None] - kpos[None, :] < window
        s_ = jnp.where(m[None, None], s_, -1e30)
        pr = jax.nn.softmax(s_, axis=-1).astype(qc.dtype)
        return jnp.einsum("bhqt,bthk->bqhk", pr, v, preferred_element_type=qc.dtype)

    def block_fused(qc, qpos0):
        """Flash-style at the XLA level: a single f32 score materialization,
        bf16 unnormalized probs into the PV matmul, and the softmax division
        deferred to the (q_chunk x head_dim)-sized output — the big [q,t]
        tensor crosses fusion boundaries once in f32 and once in bf16
        instead of ~5 f32 round-trips through jax.nn.softmax + where."""
        qpos = qpos0 + jnp.arange(qc.shape[1], dtype=jnp.int32)
        s_ = jnp.einsum("bqhk,bthk->bhqt", qc, k, preferred_element_type=jnp.float32)
        s_ = _softcap(s_ * scale, cfg.attn_logit_softcap)
        m = qpos[:, None] >= kpos[None, :]
        if window:
            m &= qpos[:, None] - kpos[None, :] < window
        s_ = s_ + jnp.where(m, 0.0, -jnp.inf)[None, None]     # additive, fusable
        mx = jax.lax.stop_gradient(jnp.max(s_, axis=-1, keepdims=True))
        p = jnp.exp(s_ - mx).astype(qc.dtype)                 # bf16 immediately
        l = jnp.sum(p.astype(jnp.float32), axis=-1)           # [b,h,q]
        o = jnp.einsum("bhqt,bthk->bqhk", p, v, preferred_element_type=jnp.float32)
        o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return o.astype(qc.dtype)

    if cfg.attn_impl == "flash":
        # Pallas flash-attention kernel: scores stay in VMEM (TPU target;
        # interpret-mode on CPU).  [B,S,H,hd] -> [B*H, S, hd].
        from repro.kernels.flash_attn import flash_attention

        bq = bk = min(max(128, cfg.q_chunk // 8), 512, S)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        of = flash_attention(qf, kf, vf, float(scale), window, bq, bk)
        o = of.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        o = shard_act(o, "batch", "seq", "act_heads", None)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"], preferred_element_type=x.dtype)
        return shard_act(out, "batch", "seq", "act_embed"), (k_kv, v_kv)

    block = block_fused if cfg.attn_impl == "fused" else block_naive

    C = min(cfg.q_chunk, S)
    # "attnscore" scope tags every score-class HLO op: on the TPU target this
    # entire region lives inside the flash-attention kernel's VMEM
    # (kernels/flash_attn.py), and the roofline classifies by this scope.
    if S <= C:
        with jax.named_scope("attnscore"):
            o = block(q, jnp.int32(0))
    else:
        nq = S // C
        qs = q.reshape(B, nq, C, H, hd).transpose(1, 0, 2, 3, 4)
        starts = (jnp.arange(nq, dtype=jnp.int32)) * C
        # checkpoint the chunk body: the scan would otherwise STACK the f32
        # probability tensors of every chunk as saved residuals for backward
        # (nq x [B,H,C,S] f32) — recomputing them is the flash-bwd trade.
        blk = block if cfg.remat == "none" else jax.checkpoint(block)

        def body(_, qc_start):
            qc, st = qc_start
            with jax.named_scope("attnscore"):
                return None, blk(qc, st)

        _, os = jax.lax.scan(body, None, (qs, starts))
        o = os.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    o = shard_act(o, "batch", "seq", "act_heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"], preferred_element_type=x.dtype)
    return shard_act(out, "batch", "seq", "act_embed"), (k_kv, v_kv)


def attention_seq(p, x, cfg, *, window: int = 0):
    out, _ = attention_seq_kv(p, x, cfg, window=window)
    return out


def attn_cache_specs(cfg, batch: int, cache_len: int, *,
                     per_slot: bool = False) -> dict[str, Spec]:
    """KV-cache layout: keys and values ``[batch, Kv, hd, cache_len]``, the
    ring's positions on lanes.  ``per_slot=True`` gives every batch row its
    own ``slot_pos`` vector ([batch, cache_len] instead of the shared
    [cache_len]) — the layout continuous batching needs so sequences at
    different positions coexist in one cache."""
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    sp_shape = (batch, cache_len) if per_slot else (cache_len,)
    sp_axes = ("cache_batch", "cache_seq") if per_slot else ("cache_seq",)
    kv_axes = ("cache_batch", "cache_kv_heads", None, "cache_seq")
    return {
        "k": ((batch, Kv, hd, cache_len), kv_axes),
        "v": ((batch, Kv, hd, cache_len), kv_axes),
        "slot_pos": (sp_shape, sp_axes),
    }


def _write_columns(k_all, v_all, k_new, v_new, layer, slot):
    """The kernel's column write; under a mesh, per shard of the cache
    (a row whose slot another shard of the ring holds writes nothing)."""
    # imported here: Pallas takes over a second to import, and only a
    # process that decodes needs it
    from repro.kernels.kv_write import kv_column_write

    mesh = current_mesh()
    if mesh is None:
        return kv_column_write(k_all, v_all, k_new, v_new, layer, slot)
    axes = ("stack", "cache_batch", "cache_kv_heads", None, "cache_seq")
    cs = logical_sharding(axes, shape=k_all.shape).spec
    cs = P(*cs, *([None] * (5 - len(cs))))
    ns, ss = P(cs[1], cs[2], None), P(cs[1])
    seq = cs[4] if isinstance(cs[4], tuple) else (cs[4],) if cs[4] else ()

    def local(k_all, v_all, k_new, v_new, layer, slot):
        w = k_all.shape[-1]
        shard = 0
        for a in seq:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        s = slot - shard * w
        s = jnp.where((s >= 0) & (s < w), s, -1)
        return kv_column_write(k_all, v_all, k_new, v_new, layer, s)

    return shard_map(local, mesh, in_specs=(cs, cs, ns, ns, P(), ss),
                     out_specs=(cs, cs))(k_all, v_all, k_new, v_new, layer, slot)


def attention_decode(p, x, cfg, cache, pos, layer, *, window: int = 0):
    """Single-token decode against layer ``layer`` of a stacked (possibly
    ring) KV cache, updated in place.

    x: [B,1,D]; cache k/v: [L,B,Kv,hd,W].  Each row's new key and value
    go into column ``pos[b] % W`` of layer ``layer`` (the one write the
    step makes: ``kernels/kv_write.py``), then the layer is read straight
    from the stacked buffer.  Two ``slot_pos`` layouts share this
    implementation, distinguished by its rank:

    * **wave batching** (``slot_pos: [L,W]``, shared): ``pos`` is a scalar
      int32 — every row writes the same ring slot and advances in
      lockstep (the legacy single-wave layout).
    * **continuous batching** (``slot_pos: [L,B,W]``, per row): ``pos``
      may be a ``[B]`` int32 vector — each row writes its own ring slot
      and masks against its own validity row, so sequences admitted
      mid-wave decode at unequal positions.

    Returns ([B,1,D], new_cache).  Grouped-query attention; the cache
    stays at Kv heads, and under a mesh each shard writes its own columns.
    """
    B = x.shape[0]
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = cfg.group_size
    W = cache["k"].shape[-1]
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k_new, v_new = _qkv(p, x, cfg, pos_v[:, None])
    slot = (pos_v % W).astype(jnp.int32)
    k_all, v_all = _write_columns(cache["k"], cache["v"], k_new[:, 0],
                                  v_new[:, 0], layer, slot)
    if cache["slot_pos"].ndim == 3:
        slot_pos = cache["slot_pos"].at[layer, jnp.arange(B), slot].set(pos_v)
        sp = slot_pos[layer]                                   # [B,W]
    else:
        slot_pos = cache["slot_pos"].at[layer, slot[0]].set(pos_v[0])
        sp = slot_pos[layer][None]                             # [1,W]
    k, v = k_all[layer], v_all[layer]                          # [B,Kv,hd,W]

    qg = q.reshape(B, Kv, G, hd)
    qg = shard_act(qg, "cache_batch", "cache_kv_heads", None, None)
    s_ = jnp.einsum("bkgd,bkdt->bkgt", qg, k, preferred_element_type=jnp.float32)
    s_ = (s_ * cfg.attention_multiplier if cfg.attention_multiplier
          else s_ / np.sqrt(hd))
    s_ = _softcap(s_, cfg.attn_logit_softcap)
    valid = (sp >= 0) & (sp <= pos_v[:, None])
    if window:
        valid &= sp > pos_v[:, None] - window
    s_ = jnp.where(valid[:, None, None, :], s_, -1e30)
    pr = jax.nn.softmax(s_, axis=-1).astype(x.dtype)
    pr = shard_act(pr, "cache_batch", "cache_kv_heads", None, "cache_seq")
    o = jnp.einsum("bkgt,bkdt->bkgd", pr, v, preferred_element_type=x.dtype)
    o = o.reshape(B, 1, H, hd)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"], preferred_element_type=x.dtype)
    return out, {"k": k_all, "v": v_all, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# MLP


def mlp_specs(cfg, hidden: int | None = None, prefix: str = "") -> dict[str, Spec]:
    D, F = cfg.d_model, hidden or cfg.d_ff
    s: dict[str, Spec] = {
        f"{prefix}w_up": ((D, F), ("embed", "ffn")),
        f"{prefix}w_down": ((F, D), ("ffn", "embed")),
    }
    if cfg.mlp_gated:
        s[f"{prefix}w_gate"] = ((D, F), ("embed", "ffn"))
    return s


def mlp(p, x, cfg, prefix: str = ""):
    up = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}w_up"], preferred_element_type=x.dtype)
    up = shard_act(up, "batch", "seq", "act_ffn")
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}w_gate"], preferred_element_type=x.dtype)
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    out = jnp.einsum("bsf,fd->bsd", h, p[f"{prefix}w_down"], preferred_element_type=x.dtype)
    return shard_act(out, "batch", "seq", "act_embed")
