"""Mamba-2 decode state step (Pallas/TPU): one token per sequence, in place.

A decode step advances every sequence's SSM state by one token.  Per
sequence b and head h, with the head's state ``S [hd, ds]``::

    S <- exp(dt[b,h] * A[h]) * S + (dt[b,h] * x[b,h,:]) (outer) B[b,:]
    y[b,h,:] = S @ C[b,:] + D[h] * x[b,h,:]

The states of every layer are one stacked float32 array ``[L, Bt, nh,
hd, ds]`` that the decode loop carries; this kernel updates layer
``layer`` of it.  The stacked state is aliased to the kernel's output and
the layer index is prefetched into scalar memory, so grid step ``b`` reads
sequence ``b``'s ``[nh, hd, ds]`` tile of that layer once and writes it
back once, and no other byte of the stack is touched.

Inside a step ``hd`` lies on sublanes and ``ds`` on lanes; ``x`` and
``y`` arrive as ``[hd, nh]`` per sequence, so head ``h`` is one column
that broadcasts along the lanes of its state.  ``models/ssm.py``'s
``_state_step_xla`` is the same step in XLA: the path off the chip and
this kernel's oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend


def _kernel(layer_ref, s_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
            y_ref, s_out):
    del layer_ref                                  # used by the index maps
    dt = dt_ref[...]                               # [1, nh]
    decay = jnp.exp(dt * a_ref[...])               # [1, nh]
    x = x_ref[...]                                 # [hd, nh]
    dtx = x * dt                                   # [hd, nh]
    bv, cv = b_ref[...], c_ref[...]                # [1, ds]
    y_ref[...] = x * d_ref[...]
    for h in range(x.shape[1]):
        s = s_ref[h] * decay[:, h:h + 1] + dtx[:, h:h + 1] * bv
        s_out[h] = s
        y_ref[:, h:h + 1] += jnp.sum(s * cv, axis=1, keepdims=True)


def ssd_state_step(state, layer, x, dt, B, C, A, D):
    """One recurrence step for layer ``layer`` of the stacked state.

    state: [L, Bt, nh, hd, ds] float32; layer: int32 scalar;
    x: [Bt, nh, hd]; dt: [Bt, nh] (after softplus); B, C: [Bt, ds];
    A: [nh] (negative); D: [nh].  Returns ``(y [Bt, nh, hd] float32,
    state)``, the state updated in place where the caller donates it (or
    it is a loop's carry)."""
    L, Bt, nh, hd, ds = state.shape
    f32 = jnp.float32

    def row(shape):
        return pl.BlockSpec((pl.Squeezed(), *shape),
                            lambda b, *_: (b,) + (0,) * len(shape))

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    st = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), nh, hd, ds),
                      lambda b, layer_ref: (layer_ref[0], b, 0, 0, 0))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Bt,),
        in_specs=[st, row((hd, nh)), row((1, nh)), row((1, ds)),
                  row((1, ds)), whole((1, nh)), whole((1, nh))],
        out_specs=[row((hd, nh)), st])
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=grid,
        out_shape=(jax.ShapeDtypeStruct((Bt, hd, nh), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=backend.pallas_interpret(),
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state,
      x.astype(f32).transpose(0, 2, 1), dt.astype(f32)[:, None],
      B.astype(f32)[:, None], C.astype(f32)[:, None],
      A.astype(f32)[None], D.astype(f32)[None])
    return y.transpose(0, 2, 1), state

