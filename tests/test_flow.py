"""Flow engine: frame v2.2 continuations, peer-to-peer chaining,
scatter/gather, error short-circuit, SLIM+NACK descriptor survival, the
dispatcher liveness floor, and the device reply-path edge cases PR 3
left thin.
"""

import struct
import time

import numpy as np
import pytest

from repro.core import Context, Status, ifunc_msg_create, poll_ifunc, \
    register_ifunc
from repro.core import frame as F
from repro.core.registry import LinkCache
from repro.flow import Chain, Flow, FlowEngine, FlowError, Hop, Scatter, \
    apply_bind, pack_chain, parse_chain
from repro.flow.descriptor import KIND_GATHER, KIND_GATHER_ARRIVAL
from repro.tasks import TaskRuntime
from repro.tasks.wire import RemoteExecutionError, pack_chunks, unpack_chunks
from repro.transport import (Dispatcher, LoopbackFabric, ProgressEngine,
                             RdmaFabric, TransportError)


# ---------------------------------------------------------------------------
# frame v2.2: the continuation section


def test_frame_cont_section_roundtrip():
    cont = b"continuation-descriptor-bytes"
    buf = F.pack_frame("f", b"CODE", b"PAYLOAD", F.CodeKind.PYBC,
                       corr_id=7, cont=cont)
    hdr = F.peek_header(buf)
    assert hdr.has_cont and hdr.corr_id == 7
    code, payload = F.frame_sections(buf, hdr)
    assert bytes(code) == b"CODE"
    assert bytes(payload) == b"PAYLOAD"      # descriptor invisible to payload
    assert bytes(F.frame_cont(buf, hdr)) == cont
    # a cont-less frame parses with an empty section and no flag
    plain = F.pack_frame("f", b"CODE", b"PAYLOAD", F.CodeKind.PYBC)
    h2 = F.peek_header(plain)
    assert not h2.has_cont and F.frame_cont(plain, h2) is None


def test_frame_cont_validation():
    # FLAG_CONT with an empty section is ill-formed
    buf = F.pack_frame("f", b"", b"p", F.CodeKind.PYBC)
    raw = bytearray(buf)
    flags_off = 60
    (flags,) = struct.unpack_from("<I", raw, flags_off)
    struct.pack_into("<I", raw, flags_off, flags | F.FLAG_CONT)
    struct.pack_into("<I", raw, F.SIGNAL_OFF,
                     F.fletcher32(bytes(raw[:F.SIGNAL_OFF])))
    with pytest.raises(F.FrameError, match="empty continuation"):
        F.peek_header(raw)
    # a reply frame must never carry a continuation
    with pytest.raises(F.FrameError):
        F.peek_header(F.pack_frame("f", b"", b"p", F.CodeKind.PYBC,
                                   flags=F.FLAG_REPLY, cont=b"x"))


def test_cont_frame_rejected_on_flow_less_target(lib_dir):
    ctx = Context("plain", lib_dir=lib_dir)
    h = register_ifunc(ctx, "task_sum")
    msg = ifunc_msg_create(h, b"\x01", cont=b"bogus-but-present")
    buf = bytearray(8 << 10)
    buf[:len(msg.frame)] = msg.frame
    assert poll_ifunc(ctx, buf, None, {}) == Status.REJECTED
    assert "flow-less" in ctx.stats["last_reject"]


# ---------------------------------------------------------------------------
# descriptor codec


def test_descriptor_roundtrip_and_errors():
    chain = Chain("origin-host", 42, (
        Hop("a", "f1", b"\x01" * 16, {"mode": "raw"}),
        Scatter((Hop("b", "f2", b"\x02" * 16, None),
                 Hop("c", "f2", b"\x02" * 16, {"mode": "static",
                                               "static": {"k": 1}}))),
        Hop("d", "f3", b"\x03" * 16, None, expect=2, gid=9, idx=0,
            kind=KIND_GATHER),
    ))
    back = parse_chain(pack_chain(chain))
    assert back == chain
    with pytest.raises(FlowError):
        parse_chain(b"\x00\x01")                 # bad magic
    with pytest.raises(FlowError):
        parse_chain(pack_chain(chain) + b"xx")   # trailing bytes
    assert apply_bind(None, b"v") == b"v"
    assert apply_bind({"mode": "kw", "key": "d", "static": {"t": 2}},
                      b"v") == {"t": 2, "d": b"v"}
    assert apply_bind({"mode": "static", "static": {"a": 1}}, b"v") == {"a": 1}
    with pytest.raises(FlowError):
        apply_bind({"mode": "nope"}, b"v")


def test_wire_chunk_framing():
    chunks = [b"", b"abc", b"\x00" * 100]
    assert unpack_chunks(pack_chunks(chunks)) == chunks
    err = RemoteExecutionError("ValueError", "boom", hop="f@peer")
    assert err.hop == "f@peer" and "at f@peer" in str(err)


# ---------------------------------------------------------------------------
# chains end to end


def _mk_engine(lib_dir, peers=("csd", "dpu", "agg")):
    eng = FlowEngine(Context("host", lib_dir=lib_dir), default_timeout=20.0)
    fabs = {"csd": LoopbackFabric(), "dpu": RdmaFabric(),
            "agg": RdmaFabric()}
    for p in peers:
        eng.add_node(p, fabs.get(p, LoopbackFabric()))
    return eng


def _blob(runs):
    return struct.pack("<I", len(runs)) + b"".join(
        struct.pack("<II", v, c) for v, c in runs)


@pytest.fixture()
def eng(lib_dir):
    return _mk_engine(lib_dir)


def test_three_stage_chain_host_never_sees_intermediates(eng):
    blob = _blob([(7, 10), (100, 5), (7, 3)])
    flow = (Flow("etl")
            .stage("csd_decompress", at="csd")
            .then("dpu_filter", at="dpu",
                  bind={"mode": "kw", "key": "data",
                        "static": {"threshold": 50}})
            .then("host_aggregate", at="agg"))
    out = eng.submit(flow, blob).result()
    assert out == {"count": 5, "sum": 500, "min": 100, "max": 100}
    # the origin sent exactly ONE frame; intermediates hopped peer-to-peer
    assert eng.origin.dispatcher.stats["sent"] == 1
    assert eng.nodes["csd"].stats["forwards"] == 1
    assert eng.nodes["dpu"].stats["forwards"] == 1
    assert eng.nodes["agg"].stats["replies"] == 1
    assert eng.pending() == 0 and eng.stats["orphan_replies"] == 0


def test_chain_goes_slim_after_warmup(eng):
    blob = _blob([(9, 4)])
    flow = (Flow("w").stage("csd_decompress", at="csd")
            .then("host_aggregate", at="agg"))
    for _ in range(3):
        assert eng.submit(flow, blob).result()["count"] == 4
    slim = sum(p.stats["slim_sent"]
               for node in eng.nodes.values()
               for p in node.dispatcher.peers.values())
    assert slim > 0        # steady-state hops ride the cached fast path


def test_scatter_gather_partial_aggregation(eng):
    from repro.tasks.graph import pack_csr_shard

    edges = {("csd", 0): [(0, 1, 0.9), (1, 0, 0.2)],
             ("dpu", 1): [(2, 3, 0.8), (3, 2, 0.7), (2, 0, 0.1)]}
    for (peer, sid), es in edges.items():
        eng.nodes[peer].target_args.setdefault("shards", {})[sid] = \
            pack_csr_shard(sid * 2, 2, es)
    q = (Flow("count")
         .scatter("graph_count", at=["csd", "dpu"],
                  binds=[{"mode": "static", "static": {"sid": 0, "wmin": 0.5}},
                         {"mode": "static", "static": {"sid": 1, "wmin": 0.5}}])
         .gather("flow_reduce", at="agg"))
    assert eng.submit(q, None).result() == 3
    agg = eng.nodes["agg"]
    assert agg.stats["gather_buffered"] == 2      # both branches rendezvoused
    assert agg.stats["gather_reduced"] == 1       # ONE reduce, at the peer
    assert not agg.gathers                        # state cleaned up
    # origin saw one reply total, not one per branch
    assert eng.stats["completed"] == 1


def test_late_gather_arrival_after_resolve_is_dropped(eng):
    """A sibling branch landing at the rendezvous AFTER its chain already
    resolved (error short-circuit won the race, or the caller cancelled)
    must not resurrect gather state that could never fill."""
    agg = eng.nodes["agg"]
    g = Hop("agg", "flow_reduce", eng.digest_of("flow_reduce"), None,
            expect=2, gid=1, idx=0, kind=KIND_GATHER_ARRIVAL)
    dead = Chain("host", 98765, (g,))     # corr has no registered future
    eng.origin.continue_chain(dead, 3)    # ships the arrival frame
    eng.drain()
    assert agg.stats.get("gather_orphans", 0) == 1
    assert not agg.gathers                # nothing resurrected


def test_scatter_must_be_followed_by_gather(eng):
    with pytest.raises(FlowError, match="followed by a gather"):
        Flow("bad").scatter("graph_count", at=["csd"]).compile(eng)
    with pytest.raises(FlowError, match="without a preceding scatter"):
        Flow("bad").gather("flow_reduce", at="agg").compile(eng)


def test_error_short_circuits_chain(eng):
    blob = _blob([(1, 2)])
    bad = (Flow("bad")
           .stage("csd_decompress", at="csd")
           .then("graph_count", at="dpu",
                 bind={"mode": "static", "static": {"sid": 99, "wmin": 0.0}})
           .then("host_aggregate", at="agg"))
    fut = eng.submit(bad, blob)
    with pytest.raises(RemoteExecutionError) as ei:
        fut.result()
    assert ei.value.hop == "graph_count@dpu"      # the failing hop travels
    assert ei.value.remote_type == "ValueError"
    # the downstream stage never executed
    assert eng.nodes["agg"].ctx.stats["executed"] == 0
    assert eng.nodes["dpu"].stats["errors"] == 1
    assert eng.pending() == 0


def test_unknown_digest_short_circuits(eng):
    """A hop pinned to a digest that matches neither the engine registry
    nor a local load dies at the forwarder, not silently elsewhere."""
    entries = (Hop("csd", "csd_decompress", eng.digest_of("csd_decompress")),
               Hop("dpu", "host_aggregate", b"\xde\xad" * 8))
    eng._corr += 1
    from repro.tasks.future import Future

    fut = Future(eng, eng._corr, "csd", "forged")
    eng.futures[eng._corr] = fut
    eng.origin.continue_chain(Chain("host", eng._corr, entries),
                              _blob([(1, 1)]))
    with pytest.raises(RemoteExecutionError, match="digest mismatch"):
        fut.result()


def test_placement_prices_hops_around_congestion(lib_dir):
    eng = _mk_engine(lib_dir, peers=("csd", "dpu", "agg"))
    eng.add_node("dpu2", RdmaFabric())
    flow = (Flow("pick")
            .stage("csd_decompress", at="csd")
            .then("dpu_filter", at=["dpu", "dpu2"],
                  bind={"mode": "kw", "key": "data",
                        "static": {"threshold": 0}})
            .then("host_aggregate", at="agg"))
    assert flow.compile(eng)[1].peer == "dpu"     # tie broken by order
    # congest csd's lane to dpu: unconsumed frames raise its queue depth
    bump = register_ifunc(eng.nodes["csd"].ctx, "counter_bump")
    for _ in range(6):
        assert eng.nodes["csd"].dispatcher.send_ifunc("dpu", bump, b"bg")
    assert flow.compile(eng)[1].peer == "dpu2"    # priced around the backlog
    assert eng.submit(flow, _blob([(5, 3)])).result()["count"] == 3
    eng.drain()


def test_flow_rejects_device_nodes(lib_dir):
    class FakeDeviceFabric:
        kind = "device"

    eng = _mk_engine(lib_dir, peers=())
    with pytest.raises(TransportError, match="device"):
        eng.add_node("tpu", FakeDeviceFabric())


# ---------------------------------------------------------------------------
# SLIM traffic carrying continuation descriptors (the NACK fallback)


def test_slim_cont_frame_survives_nack_retransmit(eng):
    """After warmup the hop frames go SLIM; evicting the digest at the
    target NACKs them — the FULL rebuild must carry the continuation
    descriptor, or the chain would lose its route."""
    blob = _blob([(60, 4)])
    flow = (Flow("nack").stage("csd_decompress", at="csd")
            .then("host_aggregate", at="agg"))
    assert eng.submit(flow, blob).result()["count"] == 4   # warm: SLIM next
    csd = eng.nodes["csd"].ctx
    dig = eng.digest_of("csd_decompress")
    assert csd.link_cache.evict("csd_decompress", dig)
    out = eng.submit(flow, blob).result()                  # SLIM -> NACK -> FULL
    assert out == {"count": 4, "sum": 240, "min": 60, "max": 60}
    origin_peer = eng.origin.dispatcher.peers["csd"]
    assert origin_peer.stats["nacks"] >= 1
    assert origin_peer.stats["resent"] >= 1
    assert eng.pending() == 0 and eng.stats["orphan_replies"] == 0


def test_lru_churn_with_cont_descriptors(lib_dir):
    """A capacity-1 link cache at the first hop churns between two chain
    ifuncs: every SLIM+cont send of the evicted digest NACKs, and every
    FULL retransmit still routes its continuation — no chain ever loses
    its reply."""
    eng = FlowEngine(Context("host", lib_dir=lib_dir), default_timeout=20.0)
    hopctx = Context("hop", lib_dir=lib_dir, link_cache=LinkCache(capacity=1))
    eng.add_node("hop", LoopbackFabric(), hopctx)
    eng.add_node("agg", RdmaFabric())
    f1 = (Flow("a").stage("csd_decompress", at="hop")
          .then("host_aggregate", at="agg"))
    f2 = (Flow("b").stage("flow_xform", at="hop")
          .then("host_aggregate", at="agg"))
    blob = _blob([(3, 2)])
    raw = struct.pack("<II", 3, 3)               # two u32 records for xform
    for _ in range(3):                           # alternate: constant churn
        assert eng.submit(f1, blob).result()["count"] == 2
        assert eng.submit(f2, raw).result()["count"] == 2
    peer = eng.origin.dispatcher.peers["hop"]
    assert peer.stats["nacks"] >= 2              # churn really NACKed
    assert peer.stats["resent"] == peer.stats["nacks"]
    assert hopctx.link_cache.stats()["evictions"] >= 4
    assert eng.pending() == 0 and eng.stats["orphan_replies"] == 0
    assert eng.stats["completed"] == 6


# ---------------------------------------------------------------------------
# dispatcher liveness floor


def _mk_runtime(lib_dir):
    rt = TaskRuntime(Context("src", lib_dir=lib_dir),
                     engine=ProgressEngine(flush_threshold=64,
                                           inflight_window="trailer"),
                     default_timeout=10.0)
    rt.add_peer("p", RdmaFabric(), Context("p", lib_dir=lib_dir),
                n_slots=4, slot_size=16 << 10, target_args={})
    return rt


def test_drain_deadline_fails_wedged_futures(lib_dir):
    rt = _mk_runtime(lib_dir)
    h = register_ifunc(rt.ctx, "task_sum")
    peer = rt.dispatcher.peers["p"]
    lane = peer.rings[0]
    lane.mailbox.sweep = lambda *a, **k: ([], None)       # the peer wedges
    futs = [rt.submit("p", h, b"\x01"), rt.submit("p", h, b"\x02")]
    t0 = time.monotonic()
    rt.drain(deadline=0.15)
    assert time.monotonic() - t0 >= 0.15
    for fut in futs:
        with pytest.raises(TransportError, match="deadline"):
            fut.result()
    assert peer.stats["timed_out"] == 2
    assert rt.dispatcher.stats["timed_out"] == 2
    assert not lane.inflight                      # records released
    assert rt.pending() == 0


def test_oldest_inflight_age_surfaces_in_stats(lib_dir):
    rt = _mk_runtime(lib_dir)
    h = register_ifunc(rt.ctx, "task_sum")
    assert rt.dispatcher.per_peer_stats()["p"]["oldest_inflight_s"] == 0.0
    peer = rt.dispatcher.peers["p"]
    peer.rings[0].mailbox.sweep = lambda *a, **k: ([], None)
    fut = rt.submit("p", h, b"\x01")
    time.sleep(0.02)
    rt.progress()
    age = rt.dispatcher.per_peer_stats()["p"]["oldest_inflight_s"]
    assert age >= 0.02
    rt.drain(deadline=0.01)                       # cleanup: fail the future
    assert fut.done()
    assert rt.dispatcher.per_peer_stats()["p"]["oldest_inflight_s"] == 0.0


def test_drain_without_deadline_unchanged(lib_dir):
    rt = _mk_runtime(lib_dir)
    h = register_ifunc(rt.ctx, "task_sum")
    fut = rt.submit("p", h, b"\x02\x03")
    rt.drain()
    assert fut.result() == 5
    assert rt.dispatcher.stats.get("timed_out", 0) == 0


# ---------------------------------------------------------------------------
# device-mesh reply-path edge cases (PR-3 coverage gap)


@pytest.fixture()
def device_rt(lib_dir):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.transport.device_fabric import DeviceMeshFabric

    T = 128
    mesh = make_mesh((len(jax.devices()),), ("model",))
    n_dev = mesh.shape["model"]
    rt = TaskRuntime(Context("src", lib_dir=lib_dir),
                     engine=ProgressEngine(inflight_window="trailer"),
                     default_timeout=60.0)
    h = register_ifunc(rt.ctx, "uvm_affine")
    W = np.eye(T, dtype=np.float32) * 2.0
    rt.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=0), None,
                n_slots=2, slot_size=128 << 10,
                prog=deserialize_uvm(h.lib.code),
                externals=jnp.broadcast_to(jnp.asarray(W)[None, None],
                                           (n_dev, 1, T, T)))
    return rt, h, T


def test_device_orphan_reply_after_cancel(device_rt):
    """A device sweep result whose future was cancelled routes as an
    orphan — counted, dropped, nothing crashes, the lane stays usable."""
    rt, h, T = device_rt
    x = np.ones((1, T, T), np.float32)
    fut = rt.submit("tpu", h, x)
    assert rt.cancel(fut)                        # caller gave up early
    rt.drain()                                   # sweep result arrives late
    assert rt.stats["orphan_replies"] == 1
    with pytest.raises(Exception):
        fut.result(timeout=0.01)
    # the lane is not poisoned: a fresh submit still resolves
    out = np.asarray(rt.submit("tpu", h, x).result())
    np.testing.assert_allclose(out[0], np.maximum(x[0] * 2.0, 0),
                               rtol=1e-4, atol=1e-5)
    assert rt.stats["orphan_replies"] == 1       # no new orphans


def test_device_duplicate_corr_reply_ignored(device_rt):
    """A duplicate (replayed) device correlation routes as an orphan and
    cannot double-resolve the future."""
    rt, h, T = device_rt
    x = np.ones((1, T, T), np.float32)
    fut = rt.submit("tpu", h, x)
    val = np.asarray(fut.result())
    # replay the same corr-id through the demux (a sweep double-report)
    rt.dispatcher._route_reply(fut.corr_id, "tpu", np.zeros(3), False,
                               decoded=True)
    assert rt.stats["orphan_replies"] == 1
    np.testing.assert_array_equal(np.asarray(fut.result()), val)


def test_device_lane_pending_corr_fails_on_deadline(device_rt):
    """fail_inflight covers device lanes: a staged-but-never-swept send's
    future resolves with a TransportError instead of hanging."""
    rt, h, T = device_rt
    lane = rt.dispatcher.peers["tpu"].rings[0]
    lane.mailbox.sweep = lambda *a, **k: ([], None)      # the mesh wedges
    fut = rt.submit("tpu", h, np.ones((1, T, T), np.float32))
    rt.drain(deadline=0.1)
    with pytest.raises(TransportError, match="device lane"):
        fut.result()
    assert not lane.inflight
