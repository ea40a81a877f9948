"""Mamba-2 SSD (state-space duality) mixer.

Train/prefill uses the chunked dual form (quadratic intra-chunk attention-like
einsums + linear inter-chunk state recurrence); decode is the O(1) recurrent
update.  Head axis shards over TP ("model"); B/C projections are group-shared
(n_groups=1) and replicated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Spec, rmsnorm
from repro.parallel.sharding import shard_act


def ssd_specs(cfg) -> dict[str, Spec]:
    D, di, ds, nh, cw = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    return {
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


def _causal_conv(x, w, state=None):
    """Depthwise causal conv, width cw, via shifted adds.

    x: [B,S,C]; w: [cw,C]; state: [B,cw-1,C] previous inputs (decode) or None.
    Returns (y [B,S,C], new_state [B,cw-1,C]).
    """
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # [B, S+cw-1, C]
    S = x.shape[1]
    y = sum(xp[:, j:j + S] * w[j] for j in range(cw))
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
    xs, _ = _causal_conv(xs, p["conv_x"])
    Bp, _ = _causal_conv(Bp, p["conv_B"])
    Cp, _ = _causal_conv(Cp, p["conv_C"])
    xs, Bp, Cp = jax.nn.silu(xs), jax.nn.silu(Bp), jax.nn.silu(Cp)
    xs = shard_act(xs, "batch", "seq", "act_ffn")

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))          # [B,S,nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                          # [nh]
    la = dt * A                                                           # log decay [B,S,nh]
    xh = xs.reshape(B, S, nh, hd)

    Q = min(cfg.ssm_chunk, S)
    nc = S // Q
    xc = xh.reshape(B, nc, Q, nh, hd)
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
        y = y.reshape(B, S, nh, hd)
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

    y = (y_intra + y_inter).reshape(B, S, nh, hd)
    y = y + xh * p["D_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["ssd_norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"], preferred_element_type=x.dtype)
    out = shard_act(out, "batch", "seq", "act_embed")
    if not want_cache:
        return out, None
    return out, {"state": hs[:, -1], "conv": conv_tail}


def ssd_decode(p, x, cfg, cache):
    """Single-step SSD.  x: [B,1,D]; cache {state [B,nh,hd,ds], conv [B,cw-1,C]}."""
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
    y, new_conv = _causal_conv(conv_in, w_all, cache["conv"])
    y = jax.nn.silu(y)
    xs, Bp, Cp = y[..., :di], y[..., di:di + ds], y[..., di + ds:]

    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))[:, 0]     # [B,nh]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * A)                                               # [B,nh]
    xh = xs.reshape(B, nh, hd).astype(jnp.float32)
    Bv = Bp[:, 0].astype(jnp.float32)                                     # [B,ds]
    Cv = Cp[:, 0].astype(jnp.float32)
    state = cache["state"].astype(jnp.float32)
    state = state * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xh, Bv)
    yh = jnp.einsum("bn,bhpn->bhp", Cv, state)
    yh = yh + xh * p["D_skip"].astype(jnp.float32)[None, :, None]
    y = yh.reshape(B, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["ssd_norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"], preferred_element_type=x.dtype)
    return out, {"state": state.astype(cache["state"].dtype), "conv": new_conv}
