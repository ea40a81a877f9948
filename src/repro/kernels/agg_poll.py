"""Aggregate-container ring-poll kernel (Pallas/TPU): device-side
validation of K-sub-record word-frame batches in one pass.

A device aggregate container packs K sub-record bodies behind a single
container header (the word-frame mirror of the host byte-layout in
core/frame.py):

    w0 magic        0x1F5C0DE6  (container magic, distinct from singleton)
    w1 n_subs       occupied sub-records (<= agg_k)
    w2 code_kind
    w3 reserved     0
    w4 hdr_check    = magic ^ n_subs ^ code_kind ^ reserved
    w5..5+2K-1      K descriptor pairs [name_hash_i, sub_check_i]
                    with sub_check_i = name_hash_i ^ SUB_SALT
    then K x body_words sub bodies (f32 tiles bit-cast), unoccupied zero
    w[slot_words-1] trailer 0xD0E1F2A3 (fixed tail position: the layout
                    is static per agg_k, unlike the singleton frame)

The kernel emits one *container* status per slot (EMPTY / READY /
INFLIGHT / BAD — same lattice as ring_poll) plus K per-sub statuses:

    SUB_EMPTY  0   i >= n_subs (or container not READY)
    SUB_READY  1   descriptor self-consistent and name_hash matches the
                   mailbox-bound program hash (bound 0 = wildcard)
    SUB_BAD    3   descriptor check mismatch — a poisoned sub-record;
                   siblings are unharmed (paper Fig. 2 per-message reject,
                   here per *sub-record*)
    SUB_NACK   4   descriptor consistent but hash does not match the bound
                   program — the device-tier cache-miss NACK: the source
                   rebuilds ONLY this record as a FULL singleton

A corrupt container header (or missing trailer) rejects the whole
container: per-sub fields cannot be trusted, exactly the host-side
``parse_agg`` signal-mismatch behaviour.

The interleaved descriptor pairs are split into a hash and a check table
by XLA before the kernel, which sees whole [n_slots, K] tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend
from repro.kernels.ring_poll import BAD, EMPTY, HDR_WORDS, INFLIGHT, READY, TRAILER, as_i32

AGG_MAGIC = 0x1F5C0DE6
SUB_SALT = 0x5A17A9E5

SUB_EMPTY, SUB_READY, SUB_BAD, SUB_NACK = 0, 1, 3, 4


def _agg_poll_kernel(bound_ref, hdr_ref, hash_ref, chk_ref, tr_ref,
                     status_ref, sub_ref):
    n, k = sub_ref.shape
    magic, n_subs, kind, rsvd, chk = (hdr_ref[:, i:i + 1]
                                      for i in range(HDR_WORDS))
    hdr_ok = (magic == AGG_MAGIC) & (chk == (magic ^ n_subs ^ kind ^ rsvd))
    bounds_ok = (n_subs >= 0) & (n_subs <= k)
    st = jnp.where(
        magic == 0, EMPTY,
        jnp.where(~(hdr_ok & bounds_ok), BAD,
                  jnp.where(tr_ref[...] == as_i32(TRAILER), READY, INFLIGHT)))
    status_ref[...] = st.astype(jnp.int32)

    hashes, checks = hash_ref[...], chk_ref[...]
    bound = bound_ref[0]
    occupied = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1) < n_subs
    ok = checks == (hashes ^ SUB_SALT)
    match = (bound == 0) | (hashes == bound)
    sub = jnp.where(ok & match, SUB_READY, jnp.where(ok, SUB_NACK, SUB_BAD))
    sub = jnp.where(occupied & (st == READY), sub, SUB_EMPTY)
    sub_ref[...] = sub.astype(jnp.int32)


def agg_ring_poll(hdr_tbl, trailers, bound):
    """Validate every aggregate slot's header block in one batched pass.

    hdr_tbl:  [n_slots, HDR_WORDS + 2K] uint32 (container hdr + descriptors)
    trailers: [n_slots, 1] uint32 (the fixed tail word of each slot)
    bound:    [1] uint32 mailbox-bound program hash (0 = wildcard)
    -> (status [n_slots] int32, sub_status [n_slots, K] int32)
    """
    n, hw = hdr_tbl.shape
    k = (hw - HDR_WORDS) // 2
    words = jax.lax.bitcast_convert_type(hdr_tbl, jnp.int32)
    desc = words[:, HDR_WORDS:HDR_WORDS + 2 * k]
    tables = (words[:, :HDR_WORDS], desc[:, 0::2], desc[:, 1::2],
              jax.lax.bitcast_convert_type(trailers, jnp.int32))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0, 0))

    status, sub = pl.pallas_call(
        _agg_poll_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
                 + [whole(t) for t in tables],
        out_specs=(pl.BlockSpec((n, 1), lambda i: (0, 0)),
                   pl.BlockSpec((n, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, k), jnp.int32)),
        interpret=backend.pallas_interpret(),
        name="agg_ring_poll",
    )(jax.lax.bitcast_convert_type(bound, jnp.int32), *tables)
    return status[:, 0], sub
