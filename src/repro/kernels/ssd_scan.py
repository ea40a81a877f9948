"""Chunked Mamba-2 SSD scan kernel (Pallas/TPU).

One (batch*head) slab per grid row; chunks iterate sequentially in the
inner grid dimension with the running SSM state carried in VMEM scratch —
the TPU-native shape of the SSD dual form: quadratic intra-chunk attention
on the MXU + O(hd x ds) inter-chunk recurrence, never materializing the
full [S, S] decay matrix.

Inputs (per bh slab, chunked):
    x   [BH, nc, Q, hd]   dt-weighted inputs (pre-multiplied by Δt)
    la  [BH, nc, Q]       log-decay  Δt·A  (negative)
    Bm  [BH, nc, Q, ds]
    Cm  [BH, nc, Q, ds]
Output:
    y   [BH, nc, Q, hd]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, state_ref):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)     # [Q, hd]
    la = la_ref[0, 0].astype(jnp.float32)   # [Q]
    B = b_ref[0, 0].astype(jnp.float32)     # [Q, ds]
    C = c_ref[0, 0].astype(jnp.float32)     # [Q, ds]
    Q = x.shape[0]

    cum = jnp.cumsum(la)                    # [Q]
    # intra-chunk: masked decay kernel on the MXU
    seg = cum[:, None] - cum[None, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    iotb = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(iota >= iotb, jnp.exp(seg), 0.0)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32) * L
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    y += jnp.exp(cum)[:, None] * jnp.dot(C, state_ref[...].T,
                                         preferred_element_type=jnp.float32)

    # state update: decay to chunk end, absorb this chunk
    tail = jnp.exp(cum[-1] - cum)           # [Q]
    state_ref[...] = (state_ref[...] * jnp.exp(cum[-1])
                      + jnp.dot((tail[:, None] * x).T, B,
                                preferred_element_type=jnp.float32))
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_hbm_bytes(B, nh, S, hd, ds, *, train: bool, dtype_bytes=2) -> float:
    """Analytic per-layer HBM traffic of the SSD kernel (roofline
    substitution): [Q,Q] decay/score tensors stay in VMEM; HBM sees the
    chunked inputs (x, la, B, C), output y, and the inter-chunk state
    stream, once forward (and ~3x for train: fwd + recompute + bwd)."""
    x_b = B * nh * S * hd * dtype_bytes
    bc_b = 2 * B * S * ds * dtype_bytes
    la_b = B * nh * S * 4
    nc = max(S // 256, 1)
    state_b = B * nc * nh * hd * ds * 4
    fwd = 2 * x_b + bc_b + la_b + state_b
    return fwd * (3.0 if train else 1.0)


@jax.jit
def ssd_scan(x, la, Bm, Cm):
    """x [BH,nc,Q,hd], la [BH,nc,Q], Bm/Cm [BH,nc,Q,ds] -> y [BH,nc,Q,hd]."""
    BH, nc, Q, hd = x.shape
    ds = Bm.shape[-1]
    return pl.pallas_call(
        _ssd_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, hd), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nc, Q, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(x, la, Bm, Cm)
