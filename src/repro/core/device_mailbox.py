"""On-device ifunc mailbox: ring buffers in device memory, deposits over
the ICI via ``ppermute`` (the RDMA-put analogue), polled/validated by the
``ring_poll`` Pallas kernel — paper Fig. 2 realized inside an SPMD program.

Word-frame layout (uint32, matches kernels/ring_poll.py):

    w0 magic | w1 frame_words | w2 code_kind | w3 name_hash | w4 hdr_check
    w5..5+frame_words-1 body (f32 payload bit-cast) | then trailer word

The μVM program itself is *bound at poll-step build time* (the device-side
hash-table-cached link): one compiled sweep handles any number of arriving
frames of that ifunc kind.  Payload tiles are carried in the frame body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.codegen import UvmProgram
from repro.kernels.ifunc_vm import ifunc_vm
from repro.kernels.ring_poll import BAD, EMPTY, HDR_WORDS, INFLIGHT, MAGIC, READY, TRAILER
from repro.kernels.ring_poll import ring_poll
from repro.parallel.sharding import shard_map


def pack_word_frame(payload_f32: np.ndarray, slot_words: int, kind: int = 3,
                    name_hash: int = 0xABC, *, corrupt: bool = False,
                    no_trailer: bool = False,
                    out: np.ndarray | None = None) -> np.ndarray:
    """Host-side framing of one device frame into a slot's word array: a
    fresh one, or ``out`` (a staged slot row) written in place.  Every word
    the poll reads is written, the trailer word too (zero when withheld),
    so a row reused from an earlier generation shows the kernels nothing
    of what it held."""
    body = np.asarray(payload_f32, np.float32).reshape(-1).view(np.uint32)
    fw = len(body)
    assert fw <= slot_words - HDR_WORDS - 1, "payload too long for slot"
    s = np.zeros(slot_words, np.uint32) if out is None else out
    chk = MAGIC ^ fw ^ kind ^ name_hash ^ (1 if corrupt else 0)
    s[:HDR_WORDS] = (MAGIC, fw, kind, name_hash, chk)
    s[HDR_WORDS:HDR_WORDS + fw] = body
    s[HDR_WORDS + fw] = 0 if no_trailer else TRAILER
    return s


def pack_agg_word_frame(payloads, hashes, agg_k: int, body_words: int,
                        slot_words: int, kind: int = 3, *,
                        corrupt: bool = False, corrupt_sub: int | None = None,
                        no_trailer: bool = False,
                        out: np.ndarray | None = None) -> np.ndarray:
    """Host-side framing of one aggregate container (K sub-record batch)
    into a slot's word array — layout in kernels/agg_poll.py — a fresh
    one, or ``out`` (a staged slot row) written in place.  ``payloads`` is
    a sequence of bodies, or one ``[n, body_words]`` array copied at once.

    Written whatever the row held: the header, all K descriptor pairs
    (those past ``n`` zero, so they read SUB_EMPTY) and the trailer word
    (zero when withheld).  Bodies past ``n`` keep what they held: the
    sweep masks their outputs.

    ``corrupt`` poisons the container header check (whole-container
    REJECT); ``corrupt_sub`` poisons one descriptor's check word (that
    sub-record alone reads SUB_BAD, siblings unharmed)."""
    from repro.kernels.agg_poll import AGG_MAGIC, SUB_SALT

    n = len(payloads)
    assert n == len(hashes) and n <= agg_k, "sub count exceeds bound agg_k"
    assert slot_words >= HDR_WORDS + 2 * agg_k + agg_k * body_words + 1
    s = np.zeros(slot_words, np.uint32) if out is None else out
    chk = AGG_MAGIC ^ n ^ kind ^ (1 if corrupt else 0)
    s[:HDR_WORDS] = (AGG_MAGIC, n, kind, 0, chk)
    desc = s[HDR_WORDS:HDR_WORDS + 2 * agg_k]
    h = (np.asarray(hashes, np.uint64) & 0xFFFFFFFF).astype(np.uint32)
    desc[0:2 * n:2] = h
    desc[1:2 * n:2] = h ^ np.uint32(SUB_SALT)
    desc[2 * n:] = 0
    if corrupt_sub is not None and corrupt_sub < n:
        desc[2 * corrupt_sub + 1] ^= 1
    off = HDR_WORDS + 2 * agg_k
    bodies = s[off:off + agg_k * body_words].reshape(agg_k, body_words)
    if isinstance(payloads, np.ndarray):
        bodies[:n] = np.asarray(payloads, np.float32).reshape(
            n, body_words).view(np.uint32)
    else:
        for i, p in enumerate(payloads):
            body = np.asarray(p, np.float32).reshape(-1).view(np.uint32)
            assert len(body) == body_words, "sub body != bound body_words"
            bodies[i] = body
    s[slot_words - 1] = 0 if no_trailer else TRAILER
    return s


def shard_rows(mesh, axis: str) -> NamedSharding:
    """Leading dim split over ``axis``: shard i of a ring lives on device i."""
    return NamedSharding(mesh, P(axis))


def empty_mailbox(mesh, axis: str, n_slots: int, slot_words: int) -> jnp.ndarray:
    """A zero ring per shard, made in place on the shard's own device."""
    return jnp.zeros((mesh.shape[axis], n_slots, slot_words), jnp.uint32,
                     device=shard_rows(mesh, axis))


def make_deposit(mesh, axis: str):
    """Build ``deposit(mailbox, outgoing, shift)``: every shard one-sided
    'puts' its outgoing slot-frames into the ring buffer of the shard
    ``shift`` hops along ``axis`` (collective_permute == the ICI RDMA put).

    Deposit is slot-masked like a real one-sided put: only slots the sender
    actually wrote (magic word != 0) land; everything else in the target
    ring — including frames from an earlier deposit not yet swept — is
    left untouched."""
    n = mesh.shape[axis]

    @functools.partial(jax.jit, static_argnames=("shift",))
    def deposit(mailbox, outgoing, shift: int):
        def f(mb, out):
            perm = [(i, (i + shift) % n) for i in range(n)]
            arrived = jax.lax.ppermute(out, axis, perm)
            written = arrived[:, :, :1] != 0          # per-slot magic present
            return jnp.where(written, arrived, mb)
        return shard_map(f, mesh, in_specs=(P(axis, None, None), P(axis, None, None)),
                         out_specs=P(axis, None, None))(mailbox, outgoing)

    return deposit


def make_sweep(mesh, axis: str, prog: UvmProgram, n_tiles: int, tile: int = 128):
    """Build ``sweep(mailbox, externals)`` -> (status, results, cleared_mb).

    Validates every slot with the ring_poll kernel, bit-casts READY frame
    bodies back to f32 payload tiles, runs the bound μVM program over them
    (masked by readiness), and clears consumed slots.
    """
    body_words = n_tiles * tile * tile

    @jax.jit
    def sweep(mailbox, ext):
        def f(mb, ext_l):
            mb2 = mb[0]                      # [n_slots, slot_words]
            with jax.named_scope("poll"):
                status = ring_poll(mb2)
            with jax.named_scope("body"):
                body = mb2[:, HDR_WORDS:HDR_WORDS + body_words]
                tiles = jax.lax.bitcast_convert_type(body, jnp.float32)
                tiles = tiles.reshape(mb2.shape[0] * n_tiles, tile, tile)
            with jax.named_scope("uvm"):
                out = ifunc_vm(prog, tiles, ext_l[0])
                out = out.reshape(mb2.shape[0], n_tiles, tile, tile)
                ready = (status == READY)
                out = out * ready[:, None, None, None].astype(out.dtype)
            with jax.named_scope("clear"):
                # READY slots are consumed; BAD (rejected) slots are cleared
                # too so a corrupt frame is reported once, not on every
                # later sweep.
                done = ready | (status == BAD)
                cleared = jnp.where(done[:, None], jnp.zeros_like(mb2), mb2)
            return status[None], out[None], cleared[None]
        return shard_map(
            f, mesh,
            in_specs=(P(axis, None, None), P(axis, None, None, None)),
            out_specs=(P(axis, None), P(axis, None, None, None), P(axis, None, None)),
        )(mailbox, ext)

    return sweep


def make_agg_sweep(mesh, axis: str, prog: UvmProgram, agg_k: int,
                   n_tiles: int, tile: int = 128, *, bound_hash: int = 0):
    """Build ``sweep(mailbox, externals)`` for *aggregate-container* slots
    -> (status, sub_status, results, cleared_mb).

    The batched amortization move: ``agg_ring_poll`` validates every
    container header + all K descriptors per slot in one kernel pass, and
    ONE ``ifunc_vm`` launch executes all n_slots x K sub-record bodies —
    per-visit fixed cost (kernel dispatch, shard_map, ppermute sync) is
    paid once per ring visit instead of once per sub-record, the device
    mirror of the host's per-put coalescing.  Non-READY sub outputs are
    masked to zero; per-sub statuses travel back for host-matching
    NACK/ERR completion."""
    from repro.kernels.agg_poll import SUB_READY, agg_ring_poll

    body_words = n_tiles * tile * tile
    hdr_words = HDR_WORDS + 2 * agg_k
    bound = np.asarray([bound_hash & 0xFFFFFFFF], np.uint32)

    @jax.jit
    def sweep(mailbox, ext):
        def f(mb, ext_l):
            mb2 = mb[0]                      # [n_slots, slot_words]
            n_slots = mb2.shape[0]
            with jax.named_scope("poll"):
                status, sub_st = agg_ring_poll(mb2[:, :hdr_words],
                                               mb2[:, -1:], bound)
            with jax.named_scope("body"):
                body = mb2[:, hdr_words:hdr_words + agg_k * body_words]
                tiles = jax.lax.bitcast_convert_type(body, jnp.float32)
                tiles = tiles.reshape(n_slots * agg_k * n_tiles, tile, tile)
            with jax.named_scope("uvm"):
                out = ifunc_vm(prog, tiles, ext_l[0])
                out = out.reshape(n_slots, agg_k, n_tiles, tile, tile)
                ready = (sub_st == SUB_READY)
                out = out * ready[:, :, None, None, None].astype(out.dtype)
            with jax.named_scope("clear"):
                done = (status == READY) | (status == BAD)
                cleared = jnp.where(done[:, None], jnp.zeros_like(mb2), mb2)
            return status[None], sub_st[None], out[None], cleared[None]
        return shard_map(
            f, mesh,
            in_specs=(P(axis, None, None), P(axis, None, None, None)),
            out_specs=(P(axis, None), P(axis, None, None),
                       P(axis, None, None, None, None), P(axis, None, None)),
        )(mailbox, ext)

    return sweep
