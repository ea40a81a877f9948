"""repro.obs: power-of-two histograms, the registry's legacy-dict aliasing,
cross-peer span lifecycle (including SLIM->NACK->FULL retransmit), the
flight-recorder ring, and the counters-only / disabled operating modes."""

import io
import json

import pytest

from repro.core import Context, register_ifunc
from repro.obs import (FlightRecorder, Histogram, Obs, Registry, Tracer,
                       delta, merge_snapshots)
from repro.obs.metrics import N_BUCKETS
from repro.transport import Dispatcher, ProgressEngine, RdmaFabric


# ---------------------------------------------------------------------------
# histogram bucket math


def test_histogram_bucket_math():
    h = Histogram("t")
    # bucket i holds v with int(v).bit_length() == i, i.e. [2^(i-1), 2^i)
    assert Histogram.bucket_of(0) == 0
    assert Histogram.bucket_of(0.5) == 0
    assert Histogram.bucket_of(1) == 1
    assert Histogram.bucket_of(1.9) == 1
    assert Histogram.bucket_of(2) == 2
    assert Histogram.bucket_of(3) == 2
    assert Histogram.bucket_of(4) == 3
    assert Histogram.bucket_of(2 ** 70) == N_BUCKETS - 1   # clamped
    for v in (0, 1, 3, 100, 100, 100):
        h.observe(v)
    assert h.count == 6
    assert h.min == 0 and h.max == 100
    assert h.mean == pytest.approx(304 / 6)
    assert h.buckets[0] == 1 and h.buckets[1] == 1 and h.buckets[2] == 1
    assert h.buckets[7] == 3                               # 100 in [64, 128)
    # quantile reports the holding bucket's upper bound (<=2x overestimate):
    # rank 3 of {0,1,3,100,100,100} is the 3, whose bucket tops out at 4
    assert h.quantile(0.5) == 4
    assert h.quantile(0.75) == 128
    assert h.quantile(1.0) == 128
    assert h.quantile(0.0) == 1                            # first non-empty


def test_histogram_empty_quantile_is_none():
    h = Histogram("t")
    assert h.quantile(0.5) is None
    assert h.mean == 0.0


def test_histogram_merge_and_snapshot_roundtrip():
    a, b = Histogram("a"), Histogram("b")
    for v in (1, 2, 4):
        a.observe(v)
    for v in (1024, 0):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert a.min == 0 and a.max == 1024
    assert a.total == pytest.approx(1031.0)
    snap = a.snapshot()
    assert snap["buckets"][11] == 1                        # 1024 in [1024, 2048)
    back = Histogram.from_snapshot("a2", snap)
    assert back.count == a.count and back.buckets == a.buckets
    assert back.quantile(0.99) == a.quantile(0.99) == 2048


# ---------------------------------------------------------------------------
# registry: aliased legacy dicts, uniquification, delta/merge


def test_registry_aliases_live_dicts_and_uniquifies():
    r = Registry("t")
    stats = {"sent": 0, "note": "not-a-number"}
    assert r.register_dict("peer.a", stats) == "peer.a"
    assert r.register_dict("peer.a", stats) == "peer.a"    # same dict: idempotent
    other = {"sent": 7}
    assert r.register_dict("peer.a", other) == "peer.a.2"  # collision: uniquified
    assert r.register_dict("peer.a", other) == "peer.a.2"  # and still idempotent
    stats["sent"] = 3                                      # live mutation, no copy
    snap = r.snapshot()
    assert snap["counters"]["peer.a.sent"] == 3
    assert snap["counters"]["peer.a.2.sent"] == 7
    assert "peer.a.note" not in snap["counters"]           # non-numeric skipped


def test_snapshot_delta_and_merge():
    r = Registry("t")
    c = r.counter("x")
    h = r.histogram("lat")
    c.inc(2)
    h.observe(10)
    prev = r.snapshot()
    c.inc(5)
    h.observe(10)
    d = delta(r.snapshot(), prev)
    assert d["counters"]["x"] == 5
    assert d["histograms"]["lat"]["count"] == 1
    merged = merge_snapshots([prev, r.snapshot()])
    assert merged["counters"]["x"] == 2 + 7
    assert merged["histograms"]["lat"]["count"] == 3


# ---------------------------------------------------------------------------
# transport integration: span lifecycle across SLIM -> NACK -> FULL


def _mk(lib_dir, obs, n_slots=4):
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, ProgressEngine(flush_threshold=64), obs=obs)
    tgt = Context("p", lib_dir=lib_dir, link_mode="remote")
    d.add_peer("p", RdmaFabric(), tgt, n_slots=n_slots, slot_size=8 << 10,
               target_args={"db": []})
    return d, tgt


def test_span_lifecycle_nack_retransmit(lib_dir):
    """One logical frame, two wire legs: the SLIM put's span closes with
    status=nack, and the FULL retransmit is a separate cat=resend span tied
    to the same corr — not a silently reopened original."""
    obs = Obs("t", trace=True)
    d, tgt = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    assert d.send_ifunc("p", h, b"first", corr_id=11)      # FULL warmup
    d.drain()
    tgt.link_cache.invalidate(h.name)                      # eviction / restart
    assert d.send_ifunc("p", h, b"second", corr_id=22)     # goes out SLIM
    d.drain()
    assert d.peers["p"].stats["nacks"] == 1
    assert d.peers["p"].stats["resent"] == 1

    tr = obs.tracer
    assert tr.open_count() == 0, [s.name for s in tr.open_spans()]
    wire = tr.spans(cat="wire")
    assert [s.args.get("status") for s in tr.spans(cat="wire", corr=11)] \
        == ["ok"]
    nacked = [s for s in wire if s.args.get("status") == "nack"]
    assert len(nacked) == 1 and nacked[0].corr == 22
    resends = tr.spans(cat="resend")
    assert len(resends) == 1
    rs = resends[0]
    assert rs.name == "resend:rle_insert@p" and rs.corr == 22
    assert rs.args.get("status") == "ok"                   # retransmit landed
    assert rs.ts >= nacked[0].ts + nacked[0].dur           # strictly after
    # the target side executed twice (warmup + retransmit), never the NACK
    assert len(tr.spans(cat="exec")) == 2
    # the recorder kept the wire story for a postmortem
    kinds = [k for _, k, _, _ in obs.recorder.events()]
    assert "nack" in kinds and "resend" in kinds and "put" in kinds


def test_chrome_export_schema(tmp_path, lib_dir):
    obs = Obs("t", trace=True)
    d, _ = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    assert d.send_ifunc("p", h, b"x", corr_id=9)
    d.drain()
    path = tmp_path / "trace.json"
    obs.tracer.export_chrome(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert meta and spans
    assert {m["args"]["name"] for m in meta} >= {"src", "p"}
    put = next(e for e in spans if e["name"].startswith("put:"))
    assert put["args"]["corr"] == 9
    assert put["dur"] >= 0 and isinstance(put["tid"], int)


# ---------------------------------------------------------------------------
# flight recorder ring


def test_flight_recorder_wraparound():
    clock_t = [0.0]
    r = FlightRecorder(capacity=4, clock=lambda: clock_t[0])
    for i in range(10):
        clock_t[0] = float(i)
        r.add("put", f"peer{i}", f"ev{i}")
    assert len(r) == 4 and r.total == 10
    assert [info for _, _, _, info in r.events()] == \
        ["ev6", "ev7", "ev8", "ev9"]                       # oldest first
    assert [info for _, _, _, info in r.last(2)] == ["ev8", "ev9"]
    text = r.format("test")
    assert "last 4 of 10 events, 6 older dropped" in text
    assert text.count("\n") == 5                           # head + 4 + tail
    r.clear()
    assert len(r) == 0 and r.total == 0


def test_flight_recorder_under_capacity():
    r = FlightRecorder(capacity=8)
    r.add("nack", "p", "one")
    assert len(r) == 1 and r.total == 1
    assert "older dropped" not in r.format()
    assert "manual" in r.format()                          # default reason
    buf = io.StringIO()
    assert r.dump("why", stream=buf) == buf.getvalue().rstrip("\n")


def test_fail_inflight_dumps_recorder(lib_dir, capsys):
    """A wedged peer's fail_inflight auto-dumps the ring: the postmortem
    names the frames that died and the reason, on stderr, unprompted."""
    obs = Obs("t")                                         # counters-only
    d, _ = _mk(lib_dir, obs)
    for r in d.peers["p"].rings:                           # peer stops consuming
        r.mailbox.sweep = lambda *a, **k: ([], None)
    h = register_ifunc(d.src_ctx, "rle_insert")
    errs = []
    d.reply_router = lambda corr, name, value, is_err, decoded: \
        errs.append((corr, is_err))
    assert d.send_ifunc("p", h, b"doomed", corr_id=404)
    assert d.fail_inflight("wedged peer") >= 1
    assert errs == [(404, True)]
    err = capsys.readouterr().err
    assert "flight recorder dump (fail_inflight: wedged peer)" in err
    assert "corr=404" in err                               # the dead frame
    assert "put" in err                                    # ...and its put event
    kinds = [k for _, k, _, _ in obs.recorder.events()]
    assert "fail_inflight" in kinds


def test_fail_inflight_dump_can_be_disabled(lib_dir, capsys):
    obs = Obs("t", dump_on_fail=False)
    d, _ = _mk(lib_dir, obs)
    for r in d.peers["p"].rings:
        r.mailbox.sweep = lambda *a, **k: ([], None)
    h = register_ifunc(d.src_ctx, "rle_insert")
    d.reply_router = lambda *a: None
    assert d.send_ifunc("p", h, b"doomed", corr_id=7)
    assert d.fail_inflight("quiet") >= 1
    assert "flight recorder dump" not in capsys.readouterr().err
    # the events are still in the ring for a manual obs.dump()
    assert any(k == "fail_inflight" for _, k, _, _ in obs.recorder.events())


# ---------------------------------------------------------------------------
# operating modes


def test_counters_only_mode_records_no_spans(lib_dir):
    """The default Obs(): histograms/counters/recorder live, tracer dark —
    begin() returns None so the hot paths carry no span objects at all."""
    obs = Obs("t")
    assert not obs.tracing
    d, _ = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    for i in range(4):
        assert d.send_ifunc("p", h, bytes([i]), corr_id=i + 1)
    d.drain()
    assert obs.tracer.begin("x") is None
    assert obs.tracer.events == [] and obs.tracer.open_count() == 0
    assert obs.rtt_hist.count == 4                         # counters still on
    assert len(obs.recorder) >= 4                          # ring still on
    snap = obs.snapshot()
    assert snap["counters"]["peer.p.sent"] == 4            # stats aliased
    assert snap["counters"]["peer.p.delivered"] == 4
    assert "peer.p.sent 4" in obs.to_text()


def test_disabled_obs_is_inert(lib_dir):
    """Obs(enabled=False) is the bench off-arm: traffic flows, nothing is
    observed anywhere — no histogram samples, no ring events, no spans."""
    obs = Obs("t", enabled=False, trace=True)              # trace loses to enabled
    d, _ = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    for i in range(3):
        assert d.send_ifunc("p", h, bytes([i]))
    d.drain()
    assert obs.rtt_hist.count == 0
    assert len(obs.recorder) == 0
    assert obs.tracer.events == []
    assert d.peers["p"].stats["delivered"] == 3            # traffic unharmed


def test_set_tracing_toggles_midrun(lib_dir):
    obs = Obs("t")
    d, _ = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    assert d.send_ifunc("p", h, b"dark")
    d.drain()
    assert obs.tracer.events == []
    obs.set_tracing(True)
    assert d.send_ifunc("p", h, b"lit")
    d.drain()
    assert obs.tracer.spans(cat="wire")
    assert obs.tracer.open_count() == 0


# ---------------------------------------------------------------------------
# layer scopes: the in-memory sink and the profiler's own trace


def _profiled(tmp_path, body):
    """Run ``body()`` inside a profiler session and a ``bench.window``
    annotation; the trace's ``bench.*`` and ``repro.*`` host events, as
    the benchmark's ``Events`` (no device planes on the CPU)."""
    import jax
    from jax.profiler import ProfileData

    from bench import trace_reduce as TR

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(TR.WINDOW):
            body()
    pd = ProfileData.from_file(TR.find_xplane(str(tmp_path)))
    host = [(e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("bench.", "repro."))]
    return TR.Events({}, {}, host)


def test_scope_records_in_memory_when_enabled():
    tr = Tracer(enabled=True)
    with tr.scope("repro.device.demux", n=3) as sc:
        sc.set_metadata(bytes=7)
    (sp,) = tr.spans(cat="scope")
    assert sp.name == "repro.device.demux" and sp.dur >= 0
    assert sp.args == {"n": 3, "bytes": 7}
    assert tr.open_count() == 0


def test_scope_writes_profiler_event_with_stats(tmp_path):
    tr = Tracer()                        # in-memory sink off: profiler only

    def body():
        with tr.scope("repro.device.publish", n=2, bytes=4096):
            pass
        with tr.scope("repro.device.demux") as sc:
            sc.set_metadata(n=5)

    ev = _profiled(tmp_path, body)
    got = {name: stats for name, _, _, stats in ev.host
           if name.startswith("repro.device.")}
    assert got["repro.device.publish"]["n"] == 2
    assert got["repro.device.publish"]["bytes"] == 4096
    assert got["repro.device.demux"]["n"] == 5
    assert tr.events == []


def test_scope_off_is_one_null_object(monkeypatch):
    import time
    import tracemalloc

    from repro.obs import NULL_SCOPE

    tr = Tracer()
    assert tr.scope("repro.a") is tr.scope("repro.b", n=1) is NULL_SCOPE

    def no_clock():
        raise AssertionError("the off path read the clock")
    monkeypatch.setattr(time, "perf_counter", no_clock)
    tracemalloc.start()
    try:
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(10_000):
            with tr.scope("repro.device.transcode", n=1, bytes=4096) as sc:
                assert sc is NULL_SCOPE
                sc.set_metadata(n=2)
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # nothing is kept: the few hundred bytes are the loop's own
    assert now - base < 1024 and peak - base < 1024
    assert tr.events == []


def test_scope_nested_under_bench_names_the_gap(tmp_path):
    """The reduction's rule over the program's scopes: a device gap that
    lies under both ``bench.submit`` and a program scope inside it is
    named after the inner scope (the benchmark's loader keeps ``bench.*``
    events only; a later benchmark change adds ``repro.*``)."""
    import time

    import jax

    from bench import trace_reduce as TR

    tr = Tracer()

    def body():
        with jax.profiler.TraceAnnotation("bench.submit"):
            time.sleep(0.002)
            with tr.scope("repro.device.publish"):
                time.sleep(0.01)
            time.sleep(0.002)

    ev = _profiled(tmp_path, body)
    (s, d) = next((s, d) for n, s, d, _ in ev.host
                  if n == "repro.device.publish")
    lo, hi = TR.window_bounds(ev)
    # the device busy up to the scope's start and from its end on: the
    # gap between is covered alike by the benchmark's span and the scope
    ev.device_ops["/device:TPU:0"] = [("%a = f32[1]{0} fusion()", lo, s - lo),
                                      ("%b = f32[1]{0} fusion()", s + d,
                                       hi - s - d)]
    red = TR.reduce(ev)
    assert red.idle_gaps[0][0] == "repro.device.publish"
    assert red.idle_gaps[0][1] == pytest.approx(d * 1e-9)


def test_host_lane_opens_no_dispatch_scopes(lib_dir):
    """The dispatcher's pack and complete scopes belong to device lanes: a
    host lane's per-message path opens none, even with tracing on (its
    engine flush still does)."""
    obs = Obs("t", trace=True)
    d, _ = _mk(lib_dir, obs)
    h = register_ifunc(d.src_ctx, "rle_insert")
    for payload in (b"a", b"b", b"c"):
        assert d.send_ifunc("p", h, payload)
        d.drain()
    names = {sp.name for sp in obs.tracer.spans(cat="scope")}
    assert "repro.engine.flush" in names
    assert not {n for n in names if n.startswith("repro.dispatch.")}
    assert d.peers["p"].stats["delivered"] == 3


def test_gc_collection_is_a_scope(tmp_path):
    import gc

    Obs("t")                             # installs the process's one hook
    Obs("u")
    assert sum(type(cb).__name__ == "_GcScope" for cb in gc.callbacks) == 1
    ev = _profiled(tmp_path, lambda: gc.collect(1))
    gcs = [stats for name, _, _, stats in ev.host if name == "repro.host.gc"]
    assert gcs and any(st.get("generation") == 1 for st in gcs)


def test_gc_scope_quiet_while_jax_imports():
    """A collection in the middle of ``import jax`` (a dispatcher made
    first, in a host-only process): the hook sees a ``jax`` module with no
    ``profiler`` yet, imports nothing and raises nothing."""
    import os
    import subprocess
    import sys

    code = ("import sys, types\n"
            "from repro.obs import Obs\n"
            "from repro.obs.trace import NULL_SCOPE, Tracer, _GcScope\n"
            "Obs('t')\n"
            "sys.modules['jax'] = types.ModuleType('jax')  # half imported\n"
            "before = set(sys.modules)\n"
            "hook = _GcScope()\n"
            "hook('start', {'generation': 0})\n"
            "hook('stop', {'generation': 0})\n"
            "assert Tracer().scope('repro.x') is NULL_SCOPE\n"
            "assert set(sys.modules) == before, set(sys.modules) - before\n"
            "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(sys.path))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr


def _device_lane(lib_dir, obs, n_slots=4):
    import jax
    import numpy as np

    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.tasks import TaskRuntime
    from repro.transport.device_fabric import DeviceMeshFabric

    T = 128
    mesh = make_mesh((len(jax.devices()),), ("model",))
    src = Context("src", lib_dir=lib_dir)
    h = register_ifunc(src, "uvm_affine")
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer",
                                       flush_threshold=2), obs=obs)
    rt = TaskRuntime(src, d)
    w = np.eye(T, dtype=np.float32)[None, None]
    rt.add_peer("tpu", DeviceMeshFabric(mesh, "model"), None,
                n_slots=n_slots, slot_size=(T * T + 64) * 4 + (64 << 10),
                prog=deserialize_uvm(h.lib.code),
                externals=np.broadcast_to(w, (mesh.shape["model"], 1, T, T)),
                n_tiles=1)
    return rt, h


def test_device_lane_scopes_bytes_and_sweep_hist(tmp_path, lib_dir):
    """A traced device lane on the CPU: every lane scope is written, the
    publish scopes' bytes are one staged generation per flush, and
    ``target.sweep_us`` observes once per sweep that consumed frames."""
    import numpy as np

    obs = Obs("t")
    rt, h = _device_lane(lib_dir, obs, n_slots=8)
    ring = rt.dispatcher.peers["tpu"].rings[0]
    ch, mb = ring.channel, ring.mailbox
    assert mb.obs is obs
    x = np.ones((1, 128, 128), np.float32)
    futs = []

    def body():
        for _ in range(3):
            futs.extend(rt.submit("tpu", h, x) for _ in range(2))
            while any(not f.done() for f in futs):
                rt.progress()

    ev = _profiled(tmp_path, body)
    assert all(f.exception() is None for f in futs)
    names = [n for n, _, _, _ in ev.host]
    for scope in ("dispatch.pack", "engine.flush", "device.transcode",
                  "device.publish", "device.readback", "device.demux",
                  "dispatch.complete"):
        assert f"repro.{scope}" in names, scope
    stats = {}
    for n, _, _, st in ev.host:
        stats.setdefault(n, []).append(st)
    generation = mb.n_shards * mb.n_slots_per_shard * mb.slot_words * 4
    pub = stats["repro.device.publish"]
    assert len(pub) == ch.stats["flushes"] == 3
    assert sum(st["bytes"] for st in pub) == generation * 3
    assert sum(st["n"] for st in pub) == 6
    assert sum(st["n"] for st in stats["repro.device.transcode"]) == 6
    # a sweep reads back every slot's int32 status, then f32 output tiles:
    # every slot's where its answers fill half the ring or more, else its
    # answers' alone, gathered on the device (``compact``; on this ring of
    # 8 slots a gather pads 1, 2 or 3 answers to no more than their count)
    back = stats["repro.device.readback"]
    slots = mb.n_shards * mb.n_slots_per_shard
    assert mb.n_shards == 1 and slots == 8
    demux = stats["repro.device.demux"]
    assert back and len(back) == len(demux)
    for st, dm in zip(back, demux):
        whole = 2 * dm["n"] >= slots
        assert st["compact"] == int(not whole), (st, dm)
        assert st["rows"] == (slots if whole else dm["n"]), (st, dm)
        assert st["bytes"] == slots * 4 + st["rows"] * 128 * 128 * 4, st
    assert any(st["compact"] for st in back), back
    consuming = sum(st["n"] > 0 for st in stats["repro.device.demux"])
    assert sum(st["n"] for st in stats["repro.device.demux"]) == 6
    assert obs.sweep_hist.count == consuming >= 3
