"""Mamba-2 SSD (state-space duality) mixer.

Train/prefill uses the chunked dual form (quadratic intra-chunk attention-like
einsums + linear inter-chunk state recurrence); a sequence whose length is
not a multiple of the chunk is padded with Δt = 0 (decay 1, no input), so
its final state is exact.  Decode is the O(1) recurrent update of one layer
of the stacked state: ``kernels/ssd_step.py`` on the chip, the XLA form
here elsewhere.  Head axis shards over TP ("model"); B/C projections are
group-shared (n_groups=1) and replicated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import backend
from repro.models.layers import Spec, rmsnorm
from repro.parallel.sharding import current_mesh, shard_act


def ssd_specs(cfg) -> dict[str, Spec]:
    D, di, ds, nh, cw = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    bias = {"conv_x_bias": ((di,), ("ffn",)),
            "conv_B_bias": ((ds,), ("ssm_state",)),
            "conv_C_bias": ((ds,), ("ssm_state",))} if cfg.ssm_conv_bias else {}
    return bias | {
        "wz": ((D, di), ("embed", "ffn")),
        "wx": ((D, di), ("embed", "ffn")),
        "wB": ((D, ds), ("embed", "ssm_state")),
        "wC": ((D, ds), ("embed", "ssm_state")),
        "wdt": ((D, nh), ("embed", "ssm_heads")),
        "conv_x": ((cw, di), (None, "ffn")),
        "conv_B": ((cw, ds), (None, "ssm_state")),
        "conv_C": ((cw, ds), (None, "ssm_state")),
        "A_log": ((nh,), ("ssm_heads",)),
        "D_skip": ((nh,), ("ssm_heads",)),
        "dt_bias": ((nh,), ("ssm_heads",)),
        "ssd_norm_scale": ((di,), ("norm",)),
        "w_out": ((di, D), ("ffn", "embed")),
    }


def ssd_cache_specs(cfg, batch: int) -> dict[str, Spec]:
    nh, hd, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = cfg.d_inner
    cw = cfg.ssm_conv
    return {
        "state": ((batch, nh, hd, ds), ("cache_batch", "ssm_heads", None, None)),
        "conv": ((batch, cw - 1, di + 2 * ds), ("cache_batch", None, "ffn")),
    }


def _causal_conv(x, w, state=None, bias=None):
    """Depthwise causal conv, width cw, via shifted adds.

    x: [B,S,C]; w: [cw,C]; state: [B,cw-1,C] previous inputs (decode) or None;
    bias: [C] or None.  Returns (y [B,S,C], new_state [B,cw-1,C]).
    """
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # [B, S+cw-1, C]
    S = x.shape[1]
    y = sum(xp[:, j:j + S] * w[j] for j in range(cw))
    if bias is not None:
        y = y + bias
    return y, xp[:, -(cw - 1):]


def _segsum(la):
    """log-decay segment sums: la [..., Q] -> [..., Q, Q] lower-tri sums."""
    Q = la.shape[-1]
    cs = jnp.cumsum(la, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), jnp.bool_), 0)
    return jnp.where(mask, d, -jnp.inf)


def ssd_seq(p, x, cfg):
    out, _ = ssd_seq_cached(p, x, cfg, want_cache=False)
    return out


def ssd_seq_cached(p, x, cfg, *, want_cache: bool = False):
    """Full-sequence SSD mixer.  x: [B,S,D] -> ([B,S,D], cache|None)."""
    B, S, D = x.shape
    nh, hd, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z = jnp.einsum("bsd,de->bse", x, p["wz"], preferred_element_type=x.dtype)
    xs = jnp.einsum("bsd,de->bse", x, p["wx"], preferred_element_type=x.dtype)
    Bp = jnp.einsum("bsd,dn->bsn", x, p["wB"], preferred_element_type=x.dtype)
    Cp = jnp.einsum("bsd,dn->bsn", x, p["wC"], preferred_element_type=x.dtype)
    dt = jnp.einsum("bsd,dh->bsh", x, p["wdt"], preferred_element_type=jnp.float32)

    conv_tail = None
    if want_cache:
        cw = cfg.ssm_conv
        raw = jnp.concatenate([xs, Bp, Cp], axis=-1)
        pad = max(0, (cw - 1) - S)
        if pad:
            raw = jnp.concatenate([jnp.zeros((B, pad, raw.shape[-1]), raw.dtype), raw], axis=1)
        conv_tail = raw[:, -(cw - 1):]
    xs, _ = _causal_conv(xs, p["conv_x"], bias=p.get("conv_x_bias"))
    Bp, _ = _causal_conv(Bp, p["conv_B"], bias=p.get("conv_B_bias"))
    Cp, _ = _causal_conv(Cp, p["conv_C"], bias=p.get("conv_C_bias"))
    xs, Bp, Cp = jax.nn.silu(xs), jax.nn.silu(Bp), jax.nn.silu(Cp)
    xs = shard_act(xs, "batch", "seq", "act_ffn")

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))          # [B,S,nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                          # [nh]
    xh = xs.reshape(B, S, nh, hd)

    Q = min(cfg.ssm_chunk, S)
    pad = -S % Q
    if pad:     # Δt = 0 past the end: decay 1 and no input, state unchanged
        def padded(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))

        xp, Bp, Cp, dt = padded(xh), padded(Bp), padded(Cp), padded(dt)
    else:
        xp = xh
    Sp = S + pad
    nc = Sp // Q
    la = dt * A                                                           # log decay [B,Sp,nh]
    xc = xp.reshape(B, nc, Q, nh, hd)
    bc = Bp.reshape(B, nc, Q, ds)
    cc = Cp.reshape(B, nc, Q, ds)
    lac = la.reshape(B, nc, Q, nh)
    dtc = dt.reshape(B, nc, Q, nh)

    if cfg.ssd_impl == "kernel":
        # Pallas ssd_scan kernel: [Q,Q] decay/score tensors stay in VMEM
        # (TPU target; interpret-mode on CPU).  x pre-weighted by Δt; B/C are
        # group-shared, broadcast per head for the [BH,...] kernel layout.
        from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel

        xk = (xc * dtc[..., None].astype(xc.dtype)) \
            .transpose(0, 3, 1, 2, 4).reshape(B * nh, nc, Q, hd)
        lak = lac.transpose(0, 3, 1, 2).reshape(B * nh, nc, Q)
        bk = jnp.broadcast_to(bc[:, None], (B, nh, nc, Q, ds)).reshape(B * nh, nc, Q, ds)
        ck = jnp.broadcast_to(cc[:, None], (B, nh, nc, Q, ds)).reshape(B * nh, nc, Q, ds)
        yk = _ssd_kernel(xk.astype(jnp.float32), lak, bk.astype(jnp.float32),
                         ck.astype(jnp.float32))
        y = yk.reshape(B, nh, nc, Q, hd).transpose(0, 2, 3, 1, 4).astype(x.dtype)
        y = y.reshape(B, Sp, nh, hd)[:, :S]
        y = y + xh * p["D_skip"].astype(x.dtype)[None, None, :, None]
        y = y.reshape(B, S, cfg.d_inner)
        y = rmsnorm(y * jax.nn.silu(z), p["ssd_norm_scale"], cfg.norm_eps)
        out = jnp.einsum("bse,ed->bsd", y, p["w_out"], preferred_element_type=x.dtype)
        out = shard_act(out, "batch", "seq", "act_embed")
        if not want_cache:
            return out, None
        # recompute the final state (cheap closed form) for serving handoff
        cum = jnp.cumsum(lac, axis=2)
        tail = jnp.exp(cum[:, :, -1:, :] - cum)
        states = jnp.einsum("bckn,bckh,bckhp->bchpn", bc.astype(jnp.float32),
                            (tail * dtc), xc.astype(jnp.float32))
        decay = jnp.exp(cum[:, :, -1, :])

        def step(h, inp):
            st, dec = inp
            return h * dec[..., None, None] + st, None

        h_fin, _ = jax.lax.scan(step, jnp.zeros((B, nh, hd, ds), jnp.float32),
                                (states.transpose(1, 0, 2, 3, 4),
                                 decay.transpose(1, 0, 2)))
        return out, {"state": h_fin, "conv": conv_tail}

    # intra-chunk (dual quadratic form) — "ssdscan" scope: on the TPU target
    # this region runs inside kernels/ssd_scan.py with the [Q,Q] decay and
    # score tensors resident in VMEM (roofline classifies by this scope)
    with jax.named_scope("ssdscan"):
        Lseg = jnp.exp(_segsum(lac.transpose(0, 1, 3, 2)))                # [B,nc,nh,Q,Q]
        scores = jnp.einsum("bcqn,bckn->bcqk", cc, bc, preferred_element_type=jnp.float32)
        M = scores[:, :, None] * Lseg                                     # [B,nc,nh,Q,Q]
        y_intra = jnp.einsum("bchqk,bckh,bckhp->bcqhp", M.astype(x.dtype),
                             dtc.astype(x.dtype), xc, preferred_element_type=x.dtype)

        # chunk-final states
        cum = jnp.cumsum(lac, axis=2)
        tail = jnp.exp(cum[:, :, -1:, :] - cum)                           # decay to chunk end
        states = jnp.einsum("bckn,bckh,bckhp->bchpn",
                            bc.astype(jnp.float32), (tail * dtc), xc.astype(jnp.float32))

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(cum[:, :, -1, :])                               # [B,nc,nh]

    def step(h, inp):
        st, dec = inp                                                     # [B,nh,hd,ds],[B,nh]
        h = h * dec[..., None, None] + st
        return h, h

    h0 = jnp.zeros((B, nh, hd, ds), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (states.transpose(1, 0, 2, 3, 4),
                                    chunk_decay.transpose(1, 0, 2)))
    hs = hs.transpose(1, 0, 2, 3, 4)                                      # [B,nc,nh,hd,ds]
    h_prev = jnp.concatenate([jnp.zeros_like(hs[:, :1]), hs[:, :-1]], axis=1)

    inter_decay = jnp.exp(cum)                                            # decay from chunk start
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", cc.astype(jnp.float32),
                         inter_decay, h_prev).astype(x.dtype)

    y = (y_intra + y_inter).reshape(B, Sp, nh, hd)[:, :S]
    y = y + xh * p["D_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["ssd_norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"], preferred_element_type=x.dtype)
    out = shard_act(out, "batch", "seq", "act_embed")
    if not want_cache:
        return out, None
    return out, {"state": hs[:, -1], "conv": conv_tail}


def _state_step_xla(state, layer, x, dt, B, C, A, D):
    """One recurrence step for layer ``layer`` of the stacked state, in XLA:
    the path off the chip and the oracle of ``kernels/ssd_step.py``, whose
    ``ssd_state_step`` has the same arguments and results."""
    f32 = jnp.float32
    s = jax.lax.dynamic_index_in_dim(state, layer, keepdims=False)
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))                                   # [B,nh]
    s = s * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, x, B.astype(f32))
    y = jnp.einsum("bn,bhpn->bhp", C.astype(f32), s) \
        + x * D.astype(f32)[None, :, None]
    return y, jax.lax.dynamic_update_index_in_dim(
        state, s.astype(state.dtype), layer, 0)


def ssd_decode(p, x, cfg, cache, layer):
    """Single-step SSD against layer ``layer`` of the stacked cache.

    x: [B,1,D]; cache {state [L,B,nh,hd,ds] f32, conv [L,B,cw-1,C]}.
    Returns ([B,1,D], the cache with that layer advanced one token).  On
    the chip the state step is ``kernels/ssd_step.py``, in place."""
    B = x.shape[0]
    nh, hd, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di = cfg.d_inner
    z = jnp.einsum("bsd,de->bse", x, p["wz"], preferred_element_type=x.dtype)
    xs = jnp.einsum("bsd,de->bse", x, p["wx"], preferred_element_type=x.dtype)
    Bp = jnp.einsum("bsd,dn->bsn", x, p["wB"], preferred_element_type=x.dtype)
    Cp = jnp.einsum("bsd,dn->bsn", x, p["wC"], preferred_element_type=x.dtype)
    dt = jnp.einsum("bsd,dh->bsh", x, p["wdt"], preferred_element_type=jnp.float32)

    conv_in = jnp.concatenate([xs, Bp, Cp], axis=-1)                      # [B,1,di+2ds]
    w_all = jnp.concatenate([p["conv_x"], p["conv_B"], p["conv_C"]], axis=-1)
    b_all = (jnp.concatenate([p["conv_x_bias"], p["conv_B_bias"],
                              p["conv_C_bias"]]) if cfg.ssm_conv_bias else None)
    conv = jax.lax.dynamic_index_in_dim(cache["conv"], layer, keepdims=False)
    y, new_conv = _causal_conv(conv_in, w_all, conv, b_all)
    y = jax.nn.silu(y)
    xs, Bp, Cp = y[..., :di], y[..., di:di + ds], y[..., di + ds:]

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))[:, 0]     # [B,nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    step = _state_step_xla
    if not backend.pallas_interpret() and current_mesh() is None:
        # imported here: Pallas takes over a second to import, and only a
        # process that decodes needs it
        from repro.kernels.ssd_step import ssd_state_step as step
    yh, state = step(cache["state"], layer, xs[:, 0].reshape(B, nh, hd), dt,
                     Bp[:, 0], Cp[:, 0], A, p["D_skip"])
    y = yh.reshape(B, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["ssd_norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"], preferred_element_type=x.dtype)
    return out, {"state": state, "conv": jax.lax.dynamic_update_index_in_dim(
        cache["conv"], new_conv.astype(cache["conv"].dtype), layer, 0)}
