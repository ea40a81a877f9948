"""Device-mesh aggregates (PR 6 tentpole b) + multi-ring striping (c).

The contracts under test:

* a FLAG_AGG byte container transcodes onto an agg-bound mesh lane as ONE
  word-frame batch whose layout matches the ``pack_agg_word_frame`` oracle;
* the batched ``agg_ring_poll`` kernel agrees with a per-slot Python
  oracle on every container/sub status, including corrupt headers,
  withheld trailers, poisoned descriptors, and hash mismatches;
* device-lane aggregate semantics match host lanes: a per-sub NACK
  triggers a FULL rebuild of that record alone (executed siblings are
  never replayed), a poisoned sub-record becomes an ERR reply with its
  siblings unharmed, and a corrupt container rejects whole;
* a striped peer keeps per-peer FIFO through a NACK/resend storm — the
  rotation and the resend quiescence gate compose.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import Context, register_ifunc  # noqa: E402
from repro.core import frame as F  # noqa: E402
from repro.core.codegen import deserialize_uvm  # noqa: E402
from repro.parallel.sharding import make_mesh  # noqa: E402
from repro.transport import Dispatcher, ProgressEngine, RdmaFabric  # noqa: E402
from repro.transport.device_fabric import DeviceMeshFabric  # noqa: E402

T = 128
K = 4


def _mk_device(lib_dir, *, agg_k=K, n_slots=2, prog_name="bind"):
    """Dispatcher with one agg-bound mesh lane executing uvm_affine
    (relu(x @ W), W = 0.5*I)."""
    mesh = make_mesh((len(jax.devices()),), ("model",))
    n_dev = mesh.shape["model"]
    src = Context("src", lib_dir=lib_dir)
    h = register_ifunc(src, "uvm_affine")
    W = np.eye(T, dtype=np.float32) * 0.5
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer"))
    d.set_coalescing(True, max_subs=agg_k, max_sub_bytes=128 << 10)
    d.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=0), None,
               n_slots=n_slots, slot_size=8 << 20,
               prog=deserialize_uvm(h.lib.code),
               externals=jnp.broadcast_to(jnp.asarray(W)[None, None],
                                          (n_dev, 1, T, T)),
               agg_k=agg_k,
               prog_name=h.lib.name if prog_name == "bind" else prog_name)
    return d, h, W


def _payloads(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((1, T, T)).astype(np.float32)
            for _ in range(n)]


def test_agg_transcode_roundtrip(lib_dir):
    """Byte container -> device put -> staged words match the
    pack_agg_word_frame oracle exactly."""
    from repro.core.device_mailbox import pack_agg_word_frame

    d, h, _ = _mk_device(lib_dir)
    mb = d.peers["tpu"].rings[0].mailbox
    ch = d.peers["tpu"].rings[0].channel
    xs = _payloads(3)
    subs = [F.AggSub(h.lib.name, h.lib.kind, h.lib.code_digest, 0,
                     x.tobytes()) for x in xs]
    buf = bytearray(mb.slot_size)
    n = F.seal_agg_frame(buf, subs, kind=subs[0].kind)
    ch.put(memoryview(buf)[:n], 0)
    want = pack_agg_word_frame(
        [x.reshape(-1) for x in xs],
        [F.fletcher32(h.lib.name.encode()) & 0xFFFFFFFF] * 3,
        mb.agg_k, mb.body_words, mb.slot_words, kind=int(h.lib.kind))
    np.testing.assert_array_equal(mb._staged[0, 0], want)


def test_agg_poll_kernel_vs_oracle(lib_dir):
    """Interpret-mode batched kernel vs a per-slot Python oracle over a
    ring mixing every container/sub state."""
    from repro.core.device_mailbox import pack_agg_word_frame
    from repro.kernels.agg_poll import (AGG_MAGIC, SUB_BAD, SUB_EMPTY,
                                        SUB_NACK, SUB_READY, SUB_SALT,
                                        agg_ring_poll)
    from repro.kernels.ring_poll import (BAD, EMPTY, HDR_WORDS, INFLIGHT,
                                         READY, TRAILER)

    body_words = T * T
    slot_words = HDR_WORDS + 2 * K + K * body_words + 1
    bound = 0xBEEF
    rng = np.random.default_rng(3)
    pay = [rng.standard_normal(body_words).astype(np.float32)
           for _ in range(K)]
    slots = np.zeros((6, slot_words), np.uint32)
    # 0: empty | 1: full READY | 2: hash-mismatch sub | 3: poisoned sub
    # 4: corrupt container | 5: trailer withheld
    slots[1] = pack_agg_word_frame(pay, [bound] * K, K, body_words, slot_words)
    slots[2] = pack_agg_word_frame(pay[:2], [bound, 0x1234], K, body_words,
                                   slot_words)
    slots[3] = pack_agg_word_frame(pay[:3], [bound] * 3, K, body_words,
                                   slot_words, corrupt_sub=1)
    slots[4] = pack_agg_word_frame(pay[:1], [bound], K, body_words,
                                   slot_words, corrupt=True)
    slots[5] = pack_agg_word_frame(pay[:2], [bound] * 2, K, body_words,
                                   slot_words, no_trailer=True)

    def oracle(slot):
        magic, n, kind, rsvd, chk = (int(slot[i]) for i in range(5))
        if magic == 0:
            return EMPTY, [SUB_EMPTY] * K
        if magic != AGG_MAGIC or chk != magic ^ n ^ kind ^ rsvd or n > K:
            return BAD, [SUB_EMPTY] * K
        if int(slot[slot_words - 1]) != TRAILER:
            return INFLIGHT, [SUB_EMPTY] * K
        st = []
        for i in range(K):
            if i >= n:
                st.append(SUB_EMPTY)
                continue
            hsh = int(slot[HDR_WORDS + 2 * i])
            ok = int(slot[HDR_WORDS + 2 * i + 1]) == hsh ^ SUB_SALT
            st.append(SUB_READY if ok and hsh == bound
                      else SUB_NACK if ok else SUB_BAD)
        return READY, st

    status, sub_st = agg_ring_poll(
        jnp.asarray(slots[:, :HDR_WORDS + 2 * K]), jnp.asarray(slots[:, -1:]),
        jnp.asarray([bound], jnp.uint32))
    for i in range(6):
        want_st, want_sub = oracle(slots[i])
        assert int(status[i]) == want_st, f"slot {i} container status"
        assert list(np.asarray(sub_st[i])) == want_sub, f"slot {i} subs"


def test_device_agg_batch_executes(lib_dir):
    """K coalesced sends ship as ONE container, execute in ONE batched
    sweep, and every result comes back correct."""
    d, h, W = _mk_device(lib_dir)
    peer = d.peers["tpu"]
    xs = _payloads(3)
    assert d.send_ifunc_many("tpu", h, xs) == 3
    assert peer.stats["agg_sent"] == 1 and peer.stats["agg_subs"] == 3
    assert d.drain() == 3
    res = peer.target_args["results"]
    assert len(res) == 3
    for r, x in zip(res, xs):
        np.testing.assert_allclose(np.asarray(r)[0],
                                   np.maximum(x[0] @ W, 0),
                                   rtol=1e-4, atol=1e-5)


def test_device_sub_nack_full_rebuild_no_sibling_replay(lib_dir):
    """A hash-mismatched sub-record NACKs alone on the mesh lane: the
    source rebuilds ONLY it as a FULL singleton; its siblings' results
    land exactly once."""
    from repro.kernels.agg_poll import SUB_SALT
    from repro.kernels.ring_poll import HDR_WORDS

    d, h, W = _mk_device(lib_dir)
    peer = d.peers["tpu"]
    mb = peer.rings[0].mailbox
    xs = _payloads(3)
    assert d.send_ifunc_many("tpu", h, xs) == 3
    # the container is staged but not yet deposited: rewrite sub 1's
    # descriptor to a *self-consistent* wrong hash — the device-tier
    # cache-miss (the program bound to this lane is not the one named)
    off = HDR_WORDS + 2 * 1
    mb._staged[0, 0, off] = 0x1234
    mb._staged[0, 0, off + 1] = 0x1234 ^ SUB_SALT
    d.drain()
    assert peer.stats["nacks"] == 1
    assert peer.stats["resent"] == 1
    assert not peer.resend
    res = peer.target_args["results"]
    assert len(res) == 3                    # 2 siblings + 1 rebuilt — no replay
    got = sorted(float(np.asarray(r).sum()) for r in res)
    want = sorted(float(np.maximum(x[0] @ W, 0).sum()) for x in xs)
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_device_poisoned_sub_err_siblings_unharmed(lib_dir):
    """A corrupt descriptor check word poisons ONE sub-record: its corr-id
    resolves with an error reply while both siblings deliver values."""
    from repro.kernels.ring_poll import HDR_WORDS

    d, h, W = _mk_device(lib_dir)
    peer = d.peers["tpu"]
    mb = peer.rings[0].mailbox
    replies = []
    d.reply_router = lambda corr, name, value, is_err, decoded: \
        replies.append((corr, value, is_err))
    xs = _payloads(3)
    assert d.send_ifunc_many("tpu", h, xs, corr_ids=[11, 12, 13]) == 3
    mb._staged[0, 0, HDR_WORDS + 2 * 1 + 1] ^= 1    # poison sub 1's check
    d.drain()
    assert sorted(c for c, _, _ in replies) == [11, 12, 13]
    by_corr = {c: (v, e) for c, v, e in replies}
    assert by_corr[12][1] and "poisoned" in str(by_corr[12][0])
    for corr, x in ((11, xs[0]), (13, xs[2])):
        val, is_err = by_corr[corr]
        assert not is_err
        np.testing.assert_allclose(np.asarray(val)[0],
                                   np.maximum(x[0] @ W, 0),
                                   rtol=1e-4, atol=1e-5)
    assert peer.stats["rejected"] == 1      # the poisoned record, not more
    assert len(peer.target_args["results"]) == 2


def test_device_corrupt_container_whole_reject(lib_dir):
    """A corrupt container header rejects the WHOLE batch: nothing
    executes, every corr-id resolves with the transport error, the slot
    clears."""
    d, h, _ = _mk_device(lib_dir)
    peer = d.peers["tpu"]
    mb = peer.rings[0].mailbox
    replies = []
    d.reply_router = lambda corr, name, value, is_err, decoded: \
        replies.append((corr, value, is_err))
    xs = _payloads(3)
    assert d.send_ifunc_many("tpu", h, xs, corr_ids=[21, 22, 23]) == 3
    mb._staged[0, 0, 4] ^= 1                # container check word
    d.drain()
    assert peer.stats["rejected"] == 1
    assert peer.target_args.get("results", []) == []
    assert sorted(c for c, _, _ in replies) == [21, 22, 23]
    assert all(is_err for _, _, is_err in replies)
    # slot cleared: the lane accepts and executes a fresh batch
    ys = _payloads(2, seed=9)
    assert d.send_ifunc_many("tpu", h, ys) == 2
    d.drain()
    assert len(peer.target_args["results"]) == 2


def test_device_singleton_on_agg_bound_lane(lib_dir):
    """A plain (non-aggregate) send still works on an agg-bound mailbox:
    it transcodes as a degenerate 1-sub container."""
    from repro.core import ifunc_msg_create

    d, h, W = _mk_device(lib_dir)
    peer = d.peers["tpu"]
    x = _payloads(1, seed=5)[0]
    assert d.send("tpu", ifunc_msg_create(h, x))
    assert d.drain() == 1
    res = peer.target_args["results"]
    assert len(res) == 1
    np.testing.assert_allclose(np.asarray(res[0])[0],
                               np.maximum(x[0] @ W, 0), rtol=1e-4, atol=1e-5)


def test_striping_fifo_under_resends(lib_dir):
    """Striped peer (rings=2) + a digest eviction mid-stream: the NACK'd
    record rebuilds FULL without replaying siblings, and every other
    record executes in program order across the rotation."""
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, ProgressEngine(flush_threshold=64))
    d.set_coalescing(True, max_subs=4)
    d.add_peer("p", RdmaFabric(),
               Context("p", lib_dir=lib_dir, link_mode="remote"),
               n_slots=2, slot_size=32 << 10, rings=2, stripe=True,
               target_args={"db": [], "count": 0})
    peer = d.peers["p"]
    h_rle = register_ifunc(src, "rle_insert")
    h_cnt = register_ifunc(src, "counter_bump")
    for h in (h_rle, h_cnt):                 # warm: FULL once each
        assert d.send_ifunc("p", h, b"\x01")
        d.drain()
    base = list(peer.target_args["db"])
    base_count = peer.target_args["count"]
    tgt = peer.target_ctx
    assert tgt.link_cache.evict("counter_bump", h_cnt.digest)
    recs = [bytes([65 + i]) * 3 for i in range(8)]
    for r in recs[:3]:
        assert d.send_ifunc("p", h_rle, r)
    assert d.send_ifunc("p", h_cnt, b"x")    # NACKs at the target
    for r in recs[3:]:
        assert d.send_ifunc("p", h_rle, r)
    deadline = 200
    while (peer.resend or any(q.subs for q in peer.coalesce.values())
           or peer.target_args["count"] < base_count + 1) and deadline:
        d.flush_coalesced("p")
        d.drain()
        deadline -= 1
    assert peer.target_args["db"] == base + recs      # FIFO across rings
    assert peer.target_args["count"] == base_count + 1  # once, not twice
    assert peer.stats["nacks"] == 1 and peer.stats["resent"] == 1
    assert peer.stripe_rx >= peer.stats["sent"] - len(peer.resend) - 2
