"""Flash attention (Pallas/TPU): online-softmax tiling, VMEM-resident scores.

This is the hardware-adaptation answer to the score-traffic wall measured in
EXPERIMENTS.md §Perf: on the XLA path the [q_chunk, S] f32 score tensor
crosses HBM ~15-20x per layer-pass; here it lives in VMEM scratch and HBM
sees only Q, K, V, O (+ dO, dQ, dK, dV and the [S] log-sum-exp row in the
backward).  Forward + backward as custom_vjp; causal and sliding-window
masks; GQA callers pre-repeat KV heads.

Layout: [BH, S, head_dim]; grid (BH, n_q_blocks, n_k_blocks) with the
k-block axis innermost (sequential) so the online-softmax state (m, l, acc)
persists in scratch across k-blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG = -1e30


def _mask(qpos, kpos, window):
    m = qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


# --------------------------------------------------------------------- fwd


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
                *, scale, window, bq, bk, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                 # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq,), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    s = jnp.where(_mask(qpos, kpos, window), s, NEG)

    m_prev, l_prev = m_s[...], l_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_prev * corr + jnp.sum(p, axis=1)
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(l)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bq", "bk"))
def _flash_fwd(q, k, v, *, scale, window, bq, bk):
    BH, S, hd = q.shape
    nq, nk = S // bq, S // bk
    grid = (BH, nq, nk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------- bwd


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_s, *, scale, window, bq, bk, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq,), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    mask = _mask(qpos, kpos, window)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    dq_s[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, scale, window, bq, bk, nq):
    i = pl.program_id(2)  # q-block axis innermost here

    @pl.when(i == 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]
    delta = delta_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq,), 0)
    kpos = pl.program_id(1) * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    mask = _mask(qpos, kpos, window)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)          # [bq, bk]
    dv_s[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "bq", "bk"))
def _flash_bwd(q, k, v, o, lse, do, *, scale, window, bq, bk):
    BH, S, hd = q.shape
    nq, nk = S // bq, S // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, nq=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), k.dtype),
            jax.ShapeDtypeStruct((BH, S, hd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------- wrapper


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, scale: float, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK):
    """q,k,v: [BH, S, hd] (KV pre-repeated to full heads).  Causal always."""
    o, _ = _flash_fwd(q, k, v, scale=scale, window=window, bq=bq, bk=bk)
    return o


def _fa_fwd(q, k, v, scale, window, bq, bk):
    o, lse = _flash_fwd(q, k, v, scale=scale, window=window, bq=bq, bk=bk)
    return o, (q, k, v, o, lse)


def _fa_bwd(scale, window, bq, bk, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, scale=scale, window=window,
                            bq=bq, bk=bk)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_hbm_bytes(B, H, S, hd, dtype_bytes=2, *, train: bool,
                    bq: int = 1024, bk: int = 512) -> float:
    """Analytic per-call HBM traffic of the kernel (roofline substitution).

    Scores never leave VMEM, but streamed blocks are re-fetched on revisit:
    with grid (b, i, j) and j innermost, K/V are read once per q-block
    (x nq) while Q/O stay put; the dkv backward kernel symmetrically re-reads
    Q/dO once per k-block (x nk).  LSE/delta rows are 4-byte.
    """
    nq = max(S // min(bq, S), 1)
    nk = max(S // min(bk, S), 1)
    t = B * H * S * hd * dtype_bytes
    row = B * H * S * 4
    fwd = t + 2 * nq * t + t + row                    # Q + KV*nq + O + lse
    if not train:
        return fwd
    bwd_dq = t + 2 * nq * t + 2 * t + 2 * row + t     # q,kv*nq,do,o? -> dq
    bwd_dkv = 2 * t + 2 * nq * t + 2 * t + 2 * row    # kv + (q,do)*nk-ish
    bwd_dkv = 2 * t + (2 * t) * nk + 2 * row + 2 * t
    delta = 2 * t + row                               # rowsum(do*o)
    return fwd + bwd_dq + bwd_dkv + delta
