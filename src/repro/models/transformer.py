"""Unified decoder stack for all assigned architectures.

The stack is a repeating ``cfg.block_pattern`` super-block scanned
``cfg.n_super`` times (plus an unrolled remainder), so heterogeneous
patterns (RecurrentGemma's R-R-A, Llama-4's dense/MoE interleave) stay
scan-compatible: every slot in the pattern has its own stacked params.

Three modes share the block implementations:

* ``train``   — full sequence, no cache.
* ``prefill`` — full sequence, emits a serving cache.
* ``decode``  — one token against the cache, carried through the layer
  loop and written one column per layer in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.parallel.sharding import shard_act

ATTN_KINDS = ("attn", "attn_moe", "attn_local")
SSD_KINDS = ("ssd", "ssd_mlp")


# ---------------------------------------------------------------------------
# specs


def _block_specs(cfg: ModelConfig, kind: str) -> dict[str, L.Spec]:
    D = cfg.d_model
    s: dict[str, L.Spec] = {}
    if kind in ATTN_KINDS:
        s.update(L.norm_specs("ln1", D))
        s.update(L.attn_specs(cfg))
        s.update(L.norm_specs("ln2", D))
        if kind == "attn_moe":
            s.update(M.moe_specs(cfg))
        else:
            s.update(L.mlp_specs(cfg))
    elif kind in SSD_KINDS:
        s.update(L.norm_specs("ln1", D))
        s.update(S.ssd_specs(cfg))
        if kind == "ssd_mlp":
            s.update(L.norm_specs("ln2", D))
            s.update(L.mlp_specs(cfg))
    elif kind == "rglru":
        s.update(L.norm_specs("ln1", D))
        s.update(R.rglru_specs(cfg))
        s.update(L.norm_specs("ln2", D))
        s.update(L.mlp_specs(cfg))
    else:
        raise ValueError(f"unknown block kind {kind}")
    return s


def _stack_specs(specs: dict[str, L.Spec], n: int) -> dict[str, L.Spec]:
    return {k: ((n, *shape), ("stack", *axes)) for k, (shape, axes) in specs.items()}


def param_specs(cfg: ModelConfig) -> dict[str, L.Spec]:
    D, V = cfg.d_model, cfg.vocab_size
    out: dict[str, L.Spec] = {"tok_embed": ((V, D), ("vocab", "embed"))}
    for slot, kind in enumerate(cfg.block_pattern):
        bs = _block_specs(cfg, kind)
        out.update({f"s{slot}_{k}": v for k, v in _stack_specs(bs, cfg.n_super).items()})
    for ti, kind in enumerate(cfg.trailing):
        bs = _block_specs(cfg, kind)
        out.update({f"t{ti}_{k}": v for k, v in bs.items()})
    out.update(L.norm_specs("final", D))
    if not cfg.tie_embeddings:
        out["lm_head"] = ((D, V), ("embed", "vocab"))
    return out


def param_shapes(cfg: ModelConfig) -> dict:
    return L.specs_shapes(param_specs(cfg), cfg.w_dtype)


def param_axes(cfg: ModelConfig) -> dict:
    return L.specs_axes(param_specs(cfg))


def init_params(cfg: ModelConfig, key) -> dict:
    return L.init_from_specs(param_specs(cfg), key, cfg.w_dtype)


def _cache_entry_specs(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                       per_slot: bool = False):
    if kind in ATTN_KINDS:
        W = min(cache_len, cfg.attn_window) if (kind == "attn_local" and cfg.attn_window) else cache_len
        return L.attn_cache_specs(cfg, batch, W, per_slot=per_slot)
    if kind in SSD_KINDS:
        return S.ssd_cache_specs(cfg, batch)
    if kind == "rglru":
        return R.rglru_cache_specs(cfg, batch)
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int, *,
                per_slot: bool = False) -> dict[str, L.Spec]:
    """``per_slot=True`` selects the continuous-batching cache layout:
    attention ``slot_pos`` carries a batch axis so every sequence tracks
    its own ring occupancy (see :func:`layers.attn_cache_specs`).  The
    default stays the shared-wave layout every existing caller uses."""
    out: dict[str, L.Spec] = {}
    for slot, kind in enumerate(cfg.block_pattern):
        es = _cache_entry_specs(cfg, kind, batch, cache_len, per_slot)
        out.update({f"s{slot}_{k}": v for k, v in _stack_specs(es, cfg.n_super).items()})
    for ti, kind in enumerate(cfg.trailing):
        es = _cache_entry_specs(cfg, kind, batch, cache_len, per_slot)
        out.update({f"t{ti}_{k}": v for k, v in es.items()})
    return out


def cache_shapes(cfg: ModelConfig, batch: int, cache_len: int, *,
                 per_slot: bool = False) -> dict:
    sp = cache_specs(cfg, batch, cache_len, per_slot=per_slot)
    out = {}
    for n, (shape, _) in sp.items():
        if n.endswith("slot_pos"):
            out[n] = jax.ShapeDtypeStruct(shape, jnp.int32)
        elif n.endswith("state") or n.endswith("h"):
            out[n] = jax.ShapeDtypeStruct(shape, jnp.float32)
        else:
            out[n] = jax.ShapeDtypeStruct(shape, cfg.act_dtype)
    return out


def cache_axes(cfg: ModelConfig, batch: int, cache_len: int, *,
               per_slot: bool = False) -> dict:
    return L.specs_axes(cache_specs(cfg, batch, cache_len, per_slot=per_slot))


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
               per_slot: bool = False) -> dict:
    out = {}
    for n, sd in cache_shapes(cfg, batch, cache_len, per_slot=per_slot).items():
        if n.endswith("slot_pos"):
            out[n] = jnp.full(sd.shape, -1, jnp.int32)
        else:
            out[n] = jnp.zeros(sd.shape, sd.dtype)
    return out


def _sub(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# block forward


def _attn_seq_with_cache(p, x, cfg, kind, want_cache: bool):
    window = cfg.attn_window if kind == "attn_local" else 0
    y, kv = L.attention_seq_kv(p, x, cfg, window=window)
    if not want_cache:
        return y, None
    k, v = (a.transpose(0, 2, 3, 1) for a in kv)      # [B,Kv,hd,S]: S on lanes
    Sq = x.shape[1]
    if window and Sq > window:
        k, v = k[..., -window:], v[..., -window:]
        slot_pos = jnp.arange(Sq - window, Sq, dtype=jnp.int32)
    else:
        slot_pos = jnp.arange(Sq, dtype=jnp.int32)
    return y, {"k": k, "v": v, "slot_pos": slot_pos}


def recurrent_keys(cfg: ModelConfig) -> tuple[str, ...]:
    """Names of the cache entries that hold recurrent state (no positions):
    a reused slot's rows of these are overwritten whole."""
    slots = [(f"s{i}_", k) for i, k in enumerate(cfg.block_pattern)]
    slots += [(f"t{i}_", k) for i, k in enumerate(cfg.trailing)]
    return tuple(pre + n for pre, kind in slots if kind not in ATTN_KINDS
                 for n in _cache_entry_specs(cfg, kind, 1, 1))


def _residual(x, y, cfg: ModelConfig):
    """x + y, the branch scaled by ``residual_multiplier`` where it is set."""
    r = cfg.residual_multiplier
    return x + (y * r if r != 1.0 else y)


def _decode_state(fn, p, h, cfg, cache, layer):
    """A recurrent block's decode against layer ``layer`` of its stacked
    state: read that layer, replace it with the block's new state."""
    c = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
         for k, v in cache.items()}
    y, nc = fn(p, h, cfg, c)
    return y, {k: jax.lax.dynamic_update_index_in_dim(
        v, nc[k].astype(v.dtype), layer, 0) for k, v in cache.items()}


def block_fwd(kind: str, cfg: ModelConfig, p: dict, x, *, mode: str, pos=None,
              cache=None, layer=None):
    """Returns (x, new_cache, aux_loss).  In decode mode ``cache`` holds the
    block's entries stacked over layers and ``layer`` is this block's index
    in them; the stacked entries come back with that layer updated."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        window = cfg.attn_window if kind == "attn_local" else 0
        h = L.rmsnorm(x, p["ln1_scale"], cfg.norm_eps)
        if mode == "decode":
            a, new_cache = L.attention_decode(p, h, cfg, cache, pos, layer,
                                              window=window)
        else:
            a, new_cache = _attn_seq_with_cache(p, h, cfg, kind, mode == "prefill")
        x = _residual(x, a, cfg)
        h = L.rmsnorm(x, p["ln2_scale"], cfg.norm_eps)
        if kind == "attn_moe":
            y, aux = M.moe_ffn(p, h, cfg)
        else:
            y = L.mlp(p, h, cfg)
        return _residual(x, y, cfg), new_cache, aux
    if kind in SSD_KINDS:
        h = L.rmsnorm(x, p["ln1_scale"], cfg.norm_eps)
        if mode == "decode":
            y, new_cache = S.ssd_decode(p, h, cfg, cache, layer)
        else:
            y, new_cache = S.ssd_seq_cached(p, h, cfg, want_cache=mode == "prefill")
        x = _residual(x, y, cfg)
        if kind == "ssd_mlp":
            h = L.rmsnorm(x, p["ln2_scale"], cfg.norm_eps)
            x = _residual(x, L.mlp(p, h, cfg), cfg)
        return x, new_cache, aux
    if kind == "rglru":
        h = L.rmsnorm(x, p["ln1_scale"], cfg.norm_eps)
        if mode == "decode":
            y, new_cache = _decode_state(R.rglru_decode, p, h, cfg, cache, layer)
        else:
            y, new_cache = R.rglru_seq_cached(p, h, cfg, want_cache=mode == "prefill")
        x = _residual(x, y, cfg)
        h = L.rmsnorm(x, p["ln2_scale"], cfg.norm_eps)
        return _residual(x, L.mlp(p, h, cfg), cfg), new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack forward


def _embed_inputs(params, inputs, cfg: ModelConfig):
    x = jnp.take(params["tok_embed"], inputs["tokens"], axis=0).astype(cfg.act_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.ext_embed_len and "ext_embed" in inputs:  # decode past the prefix: tokens only
        ext = inputs["ext_embed"].astype(cfg.act_dtype)
        x = jnp.concatenate([ext, x], axis=1)
    return shard_act(x, "batch", "seq", "act_embed")


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "block": save block boundaries only


def _decode_stack(params: dict, x, cfg: ModelConfig, cache: dict, pos):
    """Decode through every block with the whole cache as the loop's carry:
    each block updates its own layer of the stacked entries in place, so
    the cache is neither sliced out per layer nor stacked back after."""
    pattern = cfg.block_pattern

    def blocks(carry, slot_params, i):
        x, aux, cache = carry
        cache = dict(cache)
        for slot, kind in enumerate(pattern):
            pre = f"s{slot}_"
            x, nc, a = block_fwd(kind, cfg, slot_params[f"s{slot}"], x,
                                 mode="decode", pos=pos, cache=_sub(cache, pre),
                                 layer=i)
            cache.update({pre + k: v for k, v in nc.items()})
            aux = aux + a
        return x, aux, cache

    carry = (x, jnp.zeros((), jnp.float32), cache)
    if cfg.n_super > 0:
        stacked = {f"s{slot}": _sub(params, f"s{slot}_") for slot in range(len(pattern))}
        if cfg.scan_layers and cfg.n_super > 1:
            carry, _ = jax.lax.scan(
                lambda c, xs: (blocks(c, *xs), None), carry,
                (stacked, jnp.arange(cfg.n_super, dtype=jnp.int32)))
        else:
            for i in range(cfg.n_super):
                carry = blocks(carry, jax.tree.map(lambda a: a[i], stacked), i)
    x, aux, cache = carry
    cache = dict(cache)
    for ti, kind in enumerate(cfg.trailing):
        pre = f"t{ti}_"
        one = {k: v[None] for k, v in _sub(cache, pre).items()}  # a stack of one
        x, nc, a = block_fwd(kind, cfg, _sub(params, pre), x, mode="decode",
                             pos=pos, cache=one, layer=0)
        cache.update({pre + k: v[0] for k, v in nc.items()})
        aux = aux + a
    return x, cache, aux


def forward(params: dict, inputs: dict, cfg: ModelConfig, *, mode: str = "train",
            cache: dict | None = None, pos=None):
    """Run the stack.  Returns (logits, new_cache, aux_loss).

    inputs: {"tokens": [B,S] int32, optional "ext_embed": [B,L,D]}.
    decode mode: tokens is [B,1]; ``pos`` is a scalar int32 position, or a
    ``[B]`` int32 vector when the cache uses the per-slot (continuous
    batching) layout — see :func:`cache_specs`.
    """
    x = _embed_inputs(params, inputs, cfg)
    if mode == "decode":
        x, new_cache, aux_total = _decode_stack(params, x, cfg, cache, pos)
        return _logits(params, x, cfg), new_cache, aux_total
    pattern = cfg.block_pattern
    n_super = cfg.n_super
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = {}

    def super_fwd(x, slot_params):
        aux_sum = jnp.zeros((), jnp.float32)
        outs = {}
        for slot, kind in enumerate(pattern):
            x, nc, aux = block_fwd(kind, cfg, slot_params[f"s{slot}"], x, mode=mode)
            if nc is not None:
                outs[f"s{slot}"] = nc
            aux_sum = aux_sum + aux
        return x, outs, aux_sum

    if n_super > 0:
        stacked = {f"s{slot}": _sub(params, f"s{slot}_") for slot in range(len(pattern))}
        body_fn = _maybe_remat(super_fwd, cfg)

        def scan_body(carry, sp):
            x, aux = carry
            x, outs, aux_d = body_fn(x, sp)
            return (x, aux + aux_d), outs

        if cfg.scan_layers and n_super > 1:
            (x, aux_total), cache_out = jax.lax.scan(scan_body, (x, aux_total), stacked)
        else:
            cache_parts = []
            for i in range(n_super):
                sl = jax.tree.map(lambda a: a[i], stacked)
                (x, aux_total), co = scan_body((x, aux_total), sl)
                cache_parts.append(co)
            cache_out = (jax.tree.map(lambda *a: jnp.stack(a), *cache_parts)
                         if cache_parts and cache_parts[0] else {})
        if cache_out:
            for slot_name, sub in cache_out.items():
                for k, v in sub.items():
                    new_cache[f"{slot_name}_{k}"] = v

    for ti, kind in enumerate(cfg.trailing):
        x, nc, aux = block_fwd(kind, cfg, _sub(params, f"t{ti}_"), x, mode=mode)
        aux_total = aux_total + aux
        if nc is not None:
            for k, v in nc.items():
                new_cache[f"t{ti}_{k}"] = v

    return _logits(params, x, cfg), (new_cache if new_cache else None), aux_total


def _logits(params: dict, x, cfg: ModelConfig):
    x = L.rmsnorm(x, params["final_scale"], cfg.norm_eps)
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard_act(logits, "batch", "seq", "act_vocab")
