"""Unified transport layer: fabrics, progress engine, dispatcher.

Covers the contract the rest of the repo leans on: per-peer FIFO dispatch
ordering, credit exhaustion/backpressure, partial-put (IN_PROGRESS)
windows surfaced via the ProgressEngine, rejected-frame accounting per
peer, poll fairness, and completion-queue semantics.
"""

import numpy as np
import pytest

from repro.core import (CodeKind, Context, SecurityPolicy, Status,
                        ifunc_msg_create, register_ifunc)
from repro.transport import (Dispatcher, LoopbackFabric, ProgressEngine,
                             RdmaFabric, TransportError)


def _mk_dispatcher(lib_dir, peers, *, n_slots=4, slot_size=8 << 10,
                   engine=None, **peer_kw):
    """Dispatcher with one rle_insert-capable target per (name, fabric)."""
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, engine or ProgressEngine(flush_threshold=64))
    for name, fabric in peers:
        d.add_peer(name, fabric, Context(name, lib_dir=lib_dir,
                                         link_mode="remote"),
                   n_slots=n_slots, slot_size=slot_size,
                   target_args={"db": []}, **peer_kw)
    return d


@pytest.fixture()
def fanout(lib_dir):
    return _mk_dispatcher(lib_dir, [("rdma_a", RdmaFabric()),
                                    ("rdma_b", RdmaFabric()),
                                    ("loop", LoopbackFabric())])


def _record(i: int) -> bytes:
    return bytes([i % 251]) * (16 + i)


def test_multi_peer_dispatch_ordering(fanout):
    """Per-peer FIFO: every peer sees its records in exactly send order,
    across interleaved sends to three peers on two fabric kinds."""
    h = register_ifunc(fanout.src_ctx, "rle_insert")
    sent = {name: [] for name in fanout.peers}
    for i in range(12):
        for name in fanout.peers:
            rec = _record(i)
            while not fanout.send(name, ifunc_msg_create(h, rec)):
                fanout.drain()
            sent[name].append(rec)
    fanout.drain()
    for name, peer in fanout.peers.items():
        assert peer.target_args["db"] == sent[name], name
        assert peer.stats["delivered"] == 12


def test_credit_exhaustion_and_return(lib_dir):
    d = _mk_dispatcher(lib_dir, [("p", RdmaFabric())], n_slots=2)
    h = register_ifunc(d.src_ctx, "rle_insert")
    assert d.send("p", ifunc_msg_create(h, b"a"))
    assert d.send("p", ifunc_msg_create(h, b"b"))
    # ring full: send is refused, counted as backpressure, nothing clobbered
    assert not d.send("p", ifunc_msg_create(h, b"c"))
    peer = d.peers["p"]
    assert peer.stats["backpressure"] == 1
    assert peer.credits == 0
    # target drains -> credits return -> send goes through
    assert d.drain() == 2
    assert peer.credits == 2
    assert d.send("p", ifunc_msg_create(h, b"c"))
    d.drain()
    assert peer.target_args["db"] == [b"a", b"b", b"c"]
    assert peer.stats["sent"] == 3


def test_inflight_window_surfaced_via_progress_engine(lib_dir):
    """With the trailer withheld until flush, a poll inside the put window
    observes IN_PROGRESS (no execution, no head advance); flushing the
    engine publishes the trailer and the next poll consumes the frame."""
    eng = ProgressEngine(flush_threshold=64, inflight_window="trailer")
    d = _mk_dispatcher(lib_dir, [("p", RdmaFabric())], engine=eng)
    peer = d.peers["p"]
    peer.target_ctx.max_trailer_spins = 10     # don't spin long in tests
    h = register_ifunc(d.src_ctx, "rle_insert")
    handle = eng.post(peer.rings[0].channel, ifunc_msg_create(h, b"x").frame,
                      peer.rings[0].tail, peer="p")
    peer.rings[0].tail += 1
    assert not handle.done and eng.outstanding() == 1
    assert d.poll() == 0
    assert peer.stats["inflight_polls"] >= 1
    assert peer.target_args["db"] == []
    assert eng.flush() == 1                    # publishes the trailer
    assert handle.done and eng.outstanding() == 0
    assert d.poll() == 1
    assert peer.target_args["db"] == [b"x"]


def test_completion_queue_and_callbacks(lib_dir):
    eng = ProgressEngine(flush_threshold=2, inflight_window="trailer")
    d = _mk_dispatcher(lib_dir, [("p", RdmaFabric())], engine=eng)
    h = register_ifunc(d.src_ctx, "rle_insert")
    order = []
    for i in range(2):
        d.send("p", ifunc_msg_create(h, _record(i)),
               on_complete=lambda hd, i=i: order.append(i))
    # flush_threshold=2 -> the second post auto-flushed the batch
    assert eng.stats["auto_flushes"] == 1
    assert order == [0, 1]                     # callbacks in post order
    cqes = eng.poll_cq()
    assert [c.peer for c in cqes] == ["p", "p"]
    assert [c.slot for c in cqes] == [0, 1]
    assert eng.poll_cq() == []                 # drained


def test_rejected_frames_accounted_per_peer(lib_dir):
    """A PYBC frame sent to a UVM-only peer is rejected *at that peer* and
    counted there; a permissive peer receiving the same frame executes it."""
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, ProgressEngine())
    strict = Context("strict", lib_dir=lib_dir,
                     policy=SecurityPolicy(allowed_kinds=frozenset({CodeKind.UVM})))
    d.add_peer("strict", RdmaFabric(), strict, n_slots=4, slot_size=8 << 10,
               target_args={"db": []})
    d.add_peer("open", RdmaFabric(),
               Context("open", lib_dir=lib_dir, link_mode="remote"),
               n_slots=4, slot_size=8 << 10, target_args={"db": []})
    h = register_ifunc(src, "rle_insert")      # PYBC kind
    for name in ("strict", "open"):
        assert d.send(name, ifunc_msg_create(h, b"z"))
    d.drain()
    stats = d.per_peer_stats()
    assert stats["strict"]["rejected"] == 1
    assert stats["strict"]["delivered"] == 0
    assert stats["open"]["rejected"] == 0
    assert stats["open"]["delivered"] == 1
    assert strict.stats["rejected"] == 1
    # the rejected slot was cleared and its credit returned
    assert d.peers["strict"].credits == 4


def test_poll_fairness_budget_round_robin(fanout):
    """poll(budget=k) takes at most one frame per lane per round: a backlog
    on one peer cannot starve the others."""
    h = register_ifunc(fanout.src_ctx, "rle_insert")
    for i in range(3):
        fanout.send("rdma_a", ifunc_msg_create(h, _record(i)))
    fanout.send("rdma_b", ifunc_msg_create(h, b"b0"))
    fanout.send("loop", ifunc_msg_create(h, b"l0"))
    fanout.flush()
    assert fanout.poll(budget=3) == 3
    stats = fanout.per_peer_stats()
    assert stats["rdma_a"]["delivered"] == 1   # not 3: one per round
    assert stats["rdma_b"]["delivered"] == 1
    assert stats["loop"]["delivered"] == 1
    fanout.drain()
    assert fanout.per_peer_stats()["rdma_a"]["delivered"] == 3


def test_multiple_rings_per_peer(lib_dir):
    d = _mk_dispatcher(lib_dir, [("p", RdmaFabric())], n_slots=2, rings=2)
    peer = d.peers["p"]
    assert len(peer.rings) == 2 and peer.credits == 4
    h = register_ifunc(d.src_ctx, "rle_insert")
    for i in range(4):                         # fills both rings
        assert d.send("p", ifunc_msg_create(h, _record(i)))
    assert peer.credits == 0
    assert not d.send("p", ifunc_msg_create(h, b"over"))
    assert d.drain() == 4
    assert len(peer.target_args["db"]) == 4


def test_frame_too_large_for_slot(lib_dir):
    d = _mk_dispatcher(lib_dir, [("p", RdmaFabric())], slot_size=1 << 10)
    h = register_ifunc(d.src_ctx, "rle_insert")
    with pytest.raises(TransportError):
        d.send("p", ifunc_msg_create(h, bytes(range(256)) * 32))


def test_loopback_zero_copy_and_partial(lib_dir):
    """Loopback honours the same partial-delivery contract as RDMA."""
    from repro.core import poll_ifunc

    fab = LoopbackFabric()
    dst = Context("dst", lib_dir=lib_dir, link_mode="remote")
    dst.max_trailer_spins = 10
    mb = fab.open_mailbox(dst, 2, 8 << 10)
    ch = fab.connect(None, mb)
    src = Context("src", lib_dir=lib_dir)
    h = register_ifunc(src, "rle_insert")
    msg = ifunc_msg_create(h, b"partial")
    ch.put(msg.frame, 0, deliver_bytes=msg.nbytes - 3)
    db = {"db": []}
    assert poll_ifunc(dst, mb.slot_view(0), None, db) == Status.IN_PROGRESS
    ch.flush()
    assert poll_ifunc(dst, mb.slot_view(0), None, db) == Status.OK
    assert db["db"] == [b"partial"]


def test_device_fabric_through_dispatcher(lib_dir):
    """End-to-end device tier: byte frame -> word-frame transcode ->
    ppermute deposit -> compiled ring_poll/ifunc_vm sweep -> results."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.transport.device_fabric import DeviceMeshFabric

    T = 128
    mesh = make_mesh((len(jax.devices()),), ("model",))
    n_dev = mesh.shape["model"]
    src = Context("src", lib_dir=lib_dir)
    h = register_ifunc(src, "uvm_affine")
    W = np.eye(T, dtype=np.float32) * 0.5
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer"))
    d.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=0), None,
               n_slots=2, slot_size=128 << 10,
               target_args={"results": []},
               prog=deserialize_uvm(h.lib.code),
               externals=jnp.broadcast_to(jnp.asarray(W)[None, None],
                                          (n_dev, 1, T, T)))
    x = np.random.default_rng(0).standard_normal((1, T, T)).astype(np.float32)
    assert d.send("tpu", ifunc_msg_create(h, x))
    assert d.drain() == 1
    res = d.peers["tpu"].target_args["results"]
    assert len(res) == 1
    np.testing.assert_allclose(np.asarray(res[0])[0],
                               np.maximum(x[0] @ W, 0), rtol=1e-4, atol=1e-5)


def test_device_fabric_multiple_generations_no_loss(lib_dir):
    """Two flushes without an intervening sweep must not clobber the first
    generation's deposited-but-unswept frames (slot-masked deposit)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.transport.device_fabric import DeviceMeshFabric

    T = 128
    mesh = make_mesh((len(jax.devices()),), ("model",))
    n_dev = mesh.shape["model"]
    src = Context("src", lib_dir=lib_dir)
    h = register_ifunc(src, "uvm_affine")
    W = np.eye(T, dtype=np.float32)
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer"))
    d.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=0), None,
               n_slots=4, slot_size=128 << 10,
               target_args={"results": []},
               prog=deserialize_uvm(h.lib.code),
               externals=jnp.broadcast_to(jnp.asarray(W)[None, None],
                                          (n_dev, 1, T, T)))
    xs = np.random.default_rng(1).standard_normal((3, 1, T, T)).astype(np.float32)
    assert d.send("tpu", ifunc_msg_create(h, xs[0]))
    d.flush()                                  # generation 1 deposited
    for x in xs[1:]:
        assert d.send("tpu", ifunc_msg_create(h, x))
    d.flush()                                  # generation 2: must not clobber gen 1
    assert d.drain() == 3
    res = d.peers["tpu"].target_args["results"]
    assert len(res) == 3
    got = sorted(float(np.asarray(r).sum()) for r in res)
    want = sorted(float(np.maximum(x, 0).sum()) for x in xs)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert d.peers["tpu"].credits == 4 * d.peers["tpu"].rings[0].mailbox.n_shards


def _task_lane(lib_dir, kind: str, *, agg_k: int = 4):
    """A TaskRuntime with coalescing on and one peer "p": a loopback host
    lane running task_sum, or a device lane (agg-bound with ``agg_k``)
    running uvm_affine; returns (runtime, handle, payload maker)."""
    from repro.tasks import TaskRuntime

    rt = TaskRuntime(Context("src", lib_dir=lib_dir),
                     engine=ProgressEngine(inflight_window="trailer"),
                     default_timeout=60.0)
    if kind == "loopback":
        h = register_ifunc(rt.ctx, "task_sum")
        rt.dispatcher.set_coalescing(True, max_subs=4)
        rt.add_peer("p", LoopbackFabric(),
                    Context("p", lib_dir=lib_dir, link_mode="remote"),
                    n_slots=4, slot_size=16 << 10)
        return rt, h, lambda i: bytes([i + 1]) * 3
    jax = pytest.importorskip("jax")

    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.transport.device_fabric import DeviceMeshFabric

    T = 128
    mesh = make_mesh((1,), ("model",), devices=jax.devices()[:1])
    h = register_ifunc(rt.ctx, "uvm_affine")
    kw = {}
    if agg_k:
        kw = dict(agg_k=agg_k, prog_name=h.lib.name)
        rt.dispatcher.set_coalescing(True, max_subs=agg_k,
                                     max_sub_bytes=T * T * 4)
    rt.add_peer("p", DeviceMeshFabric(mesh, "model"), None, n_slots=4,
                slot_size=8 << 20, prog=deserialize_uvm(h.lib.code),
                externals=np.eye(T, dtype=np.float32)[None, None], **kw)
    return rt, h, lambda i: np.full((1, T, T), i + 1, np.float32)


@pytest.mark.parametrize("kind", ["loopback", "device"])
def test_one_inflight_table_drains_and_fails(kind, lib_dir):
    """Host and device lanes track every post in the lane's one in-flight
    table: a drain empties it, and fail_inflight resolves both a pending
    singleton's future and an aggregate's futures from it."""
    rt, h, make = _task_lane(lib_dir, kind)
    lane = rt.dispatcher.peers["p"].rings[0]
    futs = [rt.submit("p", h, make(0))]          # first delivery: FULL
    rt.drain()
    futs += rt.submit_many("p", h, [make(i) for i in range(3)])
    rt.drain()
    assert all(f.done() and f.exception() is None for f in futs)
    assert not lane.inflight
    lane.mailbox.sweep = lambda *a, **k: ([], None)      # the lane wedges
    single = rt.submit("p", h, make(5))
    rt.flush()                                   # a lone record: singleton
    agg = rt.submit_many("p", h, [make(i) for i in range(3)])
    assert len(lane.inflight) == 2
    assert sorted(len(r.subs or ()) for r in lane.inflight.values()) == [0, 3]
    assert rt.dispatcher.fail_inflight("wedged lane") == 4
    for f in [single, *agg]:
        with pytest.raises(TransportError, match="wedged lane"):
            f.result(0)
    assert not lane.inflight


def test_device_answer_not_retained_by_the_program(lib_dir):
    """An answer taken from its future is the caller's alone: once the
    caller drops it and the future, nothing in the dispatcher, the device
    lane or its target_args keeps it alive."""
    import gc
    import weakref

    rt, h, make = _task_lane(lib_dir, "device", agg_k=0)
    fut = rt.submit("p", h, make(1))
    answer = fut.result()
    np.testing.assert_allclose(np.asarray(answer), 2.0)    # relu(x @ I)
    ref = weakref.ref(answer)
    del answer, fut
    gc.collect()
    assert ref() is None


def test_controller_inject_flushes_despite_refusal(lib_dir):
    """A full mailbox on one worker must not leave frames to healthy
    workers trailer-withheld (unconsumable)."""
    from repro.core import Context as Ctx
    from repro.runtime.controller import PodController, WorkerAgent

    eng = ProgressEngine(flush_threshold=64, inflight_window="trailer")
    ctl = PodController(Ctx("ctl", lib_dir=lib_dir), engine=eng)
    healthy = WorkerAgent("healthy", Ctx("healthy", lib_dir=lib_dir),
                          n_slots=4, slot_size=8 << 10)
    stuck = WorkerAgent("stuck", Ctx("stuck", lib_dir=lib_dir),
                        n_slots=1, slot_size=8 << 10)
    ctl.attach(healthy)
    ctl.attach(stuck)
    ctl.inject("ctl_probe", b"one")            # fills stuck's single slot
    with pytest.raises(TransportError, match="stuck"):
        ctl.inject("ctl_probe", b"two")        # stuck refuses...
    healthy.ctx.max_trailer_spins = 10
    assert healthy.poll() == 2                 # ...healthy still got both
    assert healthy.hooks["acks"] == [b"one", b"two"]


def test_legacy_api_routes_through_transport(lib_dir):
    """ifunc_msg_send_nbix/poll_ring still work, now via the transport
    channel/mailbox shims (stats prove the channel carried the bytes)."""
    from repro.core import RingBuffer, ifunc_msg_send_nbix, poll_ring
    from repro.transport.fabric import endpoint_channel

    src = Context("s", lib_dir=lib_dir)
    dst = Context("d", lib_dir=lib_dir, link_mode="remote")
    region = dst.nic.mem_map(32 << 10)
    ring = RingBuffer(region, 8 << 10)
    ep = src.nic.connect(dst.nic)
    h = register_ifunc(src, "rle_insert")
    m = ifunc_msg_create(h, b"legacy")
    ifunc_msg_send_nbix(ep, m, ring.slot_addr(ring.tail), region.rkey)
    ring.tail += 1
    db = {"db": []}
    assert poll_ring(dst, ring, db) == Status.OK
    assert db["db"] == [b"legacy"]
    assert endpoint_channel(ep).stats["puts"] == 1
