"""Mailbox ring-poll kernel (Pallas/TPU): device-side frame validation.

The device mailbox (core/device_mailbox.py) stores word-oriented frames in
each ring slot:

    w0 magic        0x1F5C0DE5
    w1 frame_words  total payload words (<= slot_words - HDR - 1)
    w2 code_kind
    w3 name_hash
    w4 hdr_check    = magic ^ frame_words ^ code_kind ^ name_hash (fletcher-lite)
    w5..            body (code+payload words)
    w[5+frame_words] trailer 0xD0E1F2A3

For every slot the kernel emits a status: 0=EMPTY, 1=READY, 2=INFLIGHT
(header ok, trailer missing), 3=BAD (corrupt header / bounds) — the
device-side mirror of poll_ifunc's reject/progress logic (paper Fig. 2).

The kernel reads the first lane block (128 words) of every slot and the
one trailer word each header names, gathered by XLA: never a whole slot.
Words are handled as int32 (xor and equality do not see the sign).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import backend

MAGIC = 0x1F5C0DE5
TRAILER = 0xD0E1F2A3
HDR_WORDS = 5
LANES = 128

EMPTY, READY, INFLIGHT, BAD = 0, 1, 2, 3


def as_i32(word: int) -> int:
    """A uint32 constant as the int32 with the same bits."""
    return int(np.uint32(word).view(np.int32))


def _poll_kernel(hdr_ref, tr_ref, status_ref, *, slot_words):
    magic, fw, kind, nh, chk = (hdr_ref[:, i:i + 1] for i in range(HDR_WORDS))
    hdr_ok = (magic == MAGIC) & (chk == (magic ^ fw ^ kind ^ nh))
    # fw < 0 is a uint32 frame length of 2**31 or more: out of bounds
    bounds_ok = (fw >= 0) & (fw <= slot_words - HDR_WORDS - 1)
    st = jnp.where(
        magic == 0, EMPTY,
        jnp.where(~(hdr_ok & bounds_ok), BAD,
                  jnp.where(tr_ref[...] == as_i32(TRAILER), READY, INFLIGHT)))
    status_ref[...] = st.astype(jnp.int32)


def ring_poll(slots):
    """slots: [n_slots, slot_words] uint32 -> status [n_slots] int32."""
    n, w = slots.shape
    words = jax.lax.bitcast_convert_type(slots, jnp.int32)
    at = jnp.clip(HDR_WORDS + words[:, 1:2], 0, w - 1)
    trailer = jnp.take_along_axis(words, at, axis=1)              # [n, 1]
    lanes = min(LANES, w)
    status = pl.pallas_call(
        functools.partial(_poll_kernel, slot_words=w),
        grid=(1,),
        in_specs=[pl.BlockSpec((n, lanes), lambda i: (0, 0)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=backend.pallas_interpret(),
        name="ring_poll",
    )(words, trailer)
    return status[:, 0]
