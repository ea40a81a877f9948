"""KV-cache column write (Pallas/TPU): one new token per sequence, in place.

The decode cache keeps each layer's keys and values lane-dense, stacked
over layers: ``[L, B, Kv, hd, W]`` with the ring's W positions on lanes.
A decode step adds one token per sequence, so each layer writes one
``[Kv, hd]`` column per row, at lane ``slot[b]``.  This kernel does exactly
that and nothing else: the stacked caches are aliased to its outputs, the
layer index and the slots are prefetched into scalar memory, and grid step
``b`` reads and writes back only the ``[Kv, hd, 128]`` tile that holds row
``b``'s slot.  The rest of the cache is never touched, so the caller can
carry the whole stacked cache through its layer loop.

A negative slot writes nothing (its tile is written back unchanged): a
shard of a sequence-sharded cache passes -1 for a slot another shard
holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend

LANES = 128


def _kernel(layer_ref, slot_ref, k_ref, v_ref, kn_ref, vn_ref, k_out, v_out,
            *, tile):
    s = slot_ref[pl.program_id(0)]
    lane = s - jnp.maximum(s, 0) // tile * tile        # -1 when s < 0
    hit = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 2) == lane
    k_out[...] = jnp.where(hit, kn_ref[...], k_ref[...])
    v_out[...] = jnp.where(hit, vn_ref[...], v_ref[...])


def kv_column_write(k_cache, v_cache, k_new, v_new, layer, slots):
    """Write ``k_new[b]`` / ``v_new[b]`` at ``[layer, b, :, :, slots[b]]``.

    k_cache, v_cache: [L, B, Kv, hd, W]; k_new, v_new: [B, Kv, hd];
    layer: int32 scalar; slots: [B] int32 (negative: no write).
    Returns the two caches, updated in place where the caller donates
    them (or they are a loop's carry)."""
    L, B, Kv, hd, W = k_cache.shape
    tile = LANES if W % LANES == 0 else W

    def at(b, layer_ref, slot_ref):
        return (layer_ref[0], b, 0, 0, jnp.maximum(slot_ref[b], 0) // tile)

    cache = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), Kv, hd, tile), at)
    new = pl.BlockSpec((pl.Squeezed(), Kv, hd, 1), lambda b, *_: (b, 0, 0, 0))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[cache, cache, new, new], out_specs=[cache, cache])
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, tile=tile),
        grid_spec=grid,
        out_shape=(jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)),
        input_output_aliases={2: 0, 3: 1},
        interpret=backend.pallas_interpret(),
        name="kv_column_write",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      k_cache, v_cache,
      k_new.astype(k_cache.dtype)[..., None],
      v_new.astype(v_cache.dtype)[..., None])
