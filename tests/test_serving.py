"""Serving tier: continuous batching, the single-host server, the
disaggregated fabric, KV slab codecs, and admission backpressure."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Context, register_ifunc
from repro.models import transformer as T
from repro.serving import (TINY, ContinuousBatcher, IfuncFrontend, Request,
                           Server, ServingFabric)
from repro.serving import kv
from repro.tasks import TaskRuntime
from repro.transport import Dispatcher, ProgressEngine, RdmaFabric
from repro.transport import codec as WC


@pytest.fixture(scope="module")
def params():
    return T.init_params(TINY, jax.random.PRNGKey(0))


def _reqs(n, *, seed=11, max_new=5, plens=(4, 7, 9)):
    rng = np.random.default_rng(seed)
    return [Request(i, np.asarray(
        rng.integers(0, TINY.vocab_size, plens[i % len(plens)]), np.int32),
        max_new=max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# per-slot positions (true continuous batching)


def test_per_slot_cache_specs():
    specs = T.cache_shapes(TINY, 4, 16, per_slot=True)
    slot_pos = [v for k, v in specs.items() if k.endswith("slot_pos")]
    assert slot_pos and all(tuple(v.shape)[-2:] == (4, 16) for v in slot_pos)


def test_per_slot_decode_matches_scalar(params):
    """At uniform positions the per-slot path must reproduce the legacy
    scalar-pos decode bit for bit."""
    from repro.train import serve as SRV

    B, W, S = 2, 16, 6
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY.vocab_size, (B, S)).astype(np.int32)
    prefill = jax.jit(SRV.make_prefill_step(TINY))
    decode = jax.jit(SRV.make_decode_step(TINY))

    outs = {}
    for per_slot in (False, True):
        cache = T.init_cache(TINY, B, W, per_slot=per_slot)
        c1, last = prefill(params, {"tokens": toks})
        c1 = SRV.pad_cache_to(c1, T.cache_shapes(TINY, B, W))
        if per_slot:    # prefill emits SHARED slot_pos; broadcast per row
            c1 = {k: (jnp.broadcast_to(v[:, None], (v.shape[0], B, W))
                      if k.endswith("slot_pos") else v)
                  for k, v in c1.items()}
        cache = {k: c1[k].astype(v.dtype) for k, v in cache.items()}
        nxt = jnp.argmax(last[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos = jnp.full((B,), S, jnp.int32) if per_slot else jnp.int32(S)
        cache, logits = decode(params, cache, nxt, pos)
        outs[per_slot] = np.asarray(logits[:, -1])
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5, atol=1e-5)


def test_mid_wave_admission_unequal_pos(params):
    """A sequence joining the batch mid-wave decodes at its own position:
    the live batch holds UNEQUAL pos values and both sequences finish with
    their full token budget — wave batching can't do this."""
    from repro.train import serve as SRV

    b = ContinuousBatcher(TINY, params, batch_slots=4, cache_len=32)
    prefill = jax.jit(SRV.make_prefill_step(TINY))
    rng = np.random.default_rng(5)

    def admit(rid, plen, max_new):
        p = np.asarray(rng.integers(0, TINY.vocab_size, plen), np.int32)
        c1, last = prefill(params, {"tokens": p[None]})
        req = Request(rid, p, max_new)
        b.install(b.free_slots()[0], c1, plen, int(jnp.argmax(last[0, -1])),
                  req)
        return req

    r0 = admit(0, 9, 6)
    b.tick()
    b.tick()
    r1 = admit(1, 4, 6)          # joins while r0 is 2 tokens deep
    live = sorted(int(b.pos[s]) for s in b.active)
    assert len(set(live)) == 2, live     # genuinely mixed positions
    finished = []
    for _ in range(20):
        _, fin = b.tick()
        finished += fin
        if not b.active:
            break
    assert {r.rid for r in finished} == {0, 1}
    assert len(r0.out) == 6 and len(r1.out) == 6


_LOCAL = TINY.with_(block_pattern=("attn_local", "attn"), num_layers=5,
                    attn_window=8)          # two scanned pairs + a trailing ring


@pytest.mark.parametrize("cfg,per_slot,pos", [
    (TINY, True, [3, 7, 0, 12]),
    (TINY, False, 5),
    (_LOCAL, True, [9, 13, 8, 15]),         # the 8-wide rings have wrapped
    (TINY.with_(scan_layers=False), True, [3, 7, 0, 12]),
], ids=["per_slot", "shared_wave", "local_ring_wrapped", "unscanned"])
def test_decode_writes_one_column(cfg, per_slot, pos):
    """One decode step writes exactly one ``[Kv, hd]`` column per layer and
    row, at the row's ring slot, and leaves every other cache element
    bitwise equal."""
    from repro.train import serve as SRV

    B, W = 4, 16
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(4)
    pos_v = np.broadcast_to(np.asarray(pos, np.int32), (B,))
    old = {}
    for k, sd in T.cache_shapes(cfg, B, W, per_slot=per_slot).items():
        if k.endswith("slot_pos"):
            w = sd.shape[-1]
            rows = np.where(np.arange(w) < np.minimum(pos_v, w)[:, None],
                            np.arange(w), -1).astype(np.int32)
            old[k] = np.broadcast_to(rows if per_slot else rows[0], sd.shape)
        else:
            old[k] = np.asarray(rng.standard_normal(sd.shape), sd.dtype)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    step = jax.jit(SRV.make_decode_step(cfg))
    new, _ = step(params, {k: jnp.asarray(v) for k, v in old.items()}, toks,
                  jnp.asarray(pos, jnp.int32))
    assert set(new) == set(old)
    for k, was in old.items():
        got = np.asarray(new[k])
        w = got.shape[-1]
        want = np.zeros(got.shape, bool)
        for b, p in enumerate(pos_v):
            if k.endswith("slot_pos"):
                want[..., b if per_slot else slice(None), p % w] = True
            else:
                want[..., b, :, :, p % w] = True
        nbytes = got.dtype.itemsize
        same = (got.view(np.uint8).reshape(*got.shape, nbytes)
                == was.view(np.uint8).reshape(*got.shape, nbytes)).all(-1)
        assert same[~want].all(), f"{k}: written outside its columns"
        if k.endswith("slot_pos"):
            assert (got[want] == np.broadcast_to(
                pos_v[:, None] if per_slot else pos_v[0], got.shape)[want]).all()
            continue
        for b, p in enumerate(pos_v):       # each layer's column is written
            col = same[..., b, :, :, p % w]                 # [(L,) Kv, hd]
            per_layer = col.reshape(-1, col.shape[-2] * col.shape[-1])
            assert not per_layer.all(-1).any(), f"{k}: a column unwritten"


# ---------------------------------------------------------------------------
# KV slab wire format


def test_kv_slab_roundtrip():
    rng = np.random.default_rng(2)
    entries = {"s0_k": rng.standard_normal((1, 1, 1, 4, 8)).astype(np.float32),
               "s0_v": rng.standard_normal((1, 1, 1, 4, 8)).astype(np.float32),
               "s0_slot_pos": np.arange(8, dtype=np.int32)}   # elided
    slab = kv.pack_kv(entries, rid=7, slot=3, pos0=5, first_token=42)
    assert kv.peek_kv(slab) == (7, 3)
    got = kv.unpack_kv(slab)
    assert (got["rid"], got["slot"], got["pos0"],
            got["first_token"]) == (7, 3, 5, 42)
    assert set(got["entries"]) == {"s0_k", "s0_v"}
    np.testing.assert_array_equal(got["entries"]["s0_k"], entries["s0_k"])
    shapes = {k: v for k, v in entries.items()}
    assert kv.slab_bytes(shapes) == len(slab)


def test_kv_quant8_stream_roundtrip(params):
    """A real prefilled KV slab streamed under the lossy ``quant8`` wire
    codec lands within quantization tolerance: chunk 0 (the peekable
    header) ships bit-exact, the f32 body dequantizes to ~1/127 of each
    chunk's max magnitude."""
    from repro.train import serve as SRV

    prompt = np.arange(1, 9, dtype=np.int32)
    prefill = jax.jit(SRV.make_prefill_step(TINY))
    cache1, _ = prefill(params, {"tokens": prompt[None]})
    entries = {k: np.asarray(v, np.float32) for k, v in cache1.items()
               if not k.endswith("slot_pos")}
    slab = kv.pack_kv(entries, rid=1, slot=0, pos0=8, first_token=9)

    src, dst = Context("src"), Context("dst")
    sink = {"slabs": {0: bytearray(len(slab))}, "kv_arrivals": [],
            "counters": {"buffered_installs": 0}}
    rt = TaskRuntime(src, Dispatcher(src, ProgressEngine(flush_threshold=2)))
    rt.dispatcher.set_streaming(True, chunk_bytes=4 << 10, window=2,
                                threshold=1 << 10)
    rt.add_peer("dst", RdmaFabric(), dst, n_slots=4, slot_size=16 << 10,
                target_args=sink, codec="quant8")
    h = register_ifunc(src, "kv_install")
    fut = rt.submit("dst", h, slab)
    rt.drain(deadline=5.0)
    ack = fut.result(timeout=5.0)
    assert ack["streamed"] and ack["rid"] == 1
    assert sink["counters"]["buffered_installs"] == 0

    got = kv.unpack_kv(bytes(sink["slabs"][0]))
    assert (got["rid"], got["slot"], got["pos0"],
            got["first_token"]) == (1, 0, 8, 9)       # header bit-exact
    for k, ref in entries.items():
        arr = got["entries"][k]
        tol = float(np.max(np.abs(ref))) / 127.0 + 1e-6
        np.testing.assert_allclose(arr, ref, atol=tol)


def test_codec_lossy_flags():
    assert not WC.get_codec("raw").lossy
    assert not WC.get_codec("rle").lossy
    assert WC.get_codec("quant8").lossy


# ---------------------------------------------------------------------------
# admission backpressure (satellite: srv_enqueue under credit exhaustion)


def test_enqueue_backpressure_no_leak():
    """A frontend outrunning the server: ``submit`` returns None once ring
    credits run out, no queued request is overwritten, and the refused
    submits never leak futures in the corr table."""
    server_ctx = Context("server")
    fe = IfuncFrontend(server_ctx, n_slots=2)
    reqs = _reqs(6, max_new=3, plens=(4,))
    futs, refused = [], 0
    for r in reqs:
        f = fe.submit(r)
        if f is None:
            refused += 1
        else:
            futs.append(f)
    assert refused > 0 and futs                       # both behaviors seen
    # the corr table holds exactly the accepted submits — refused ones
    # were unregistered on the spot
    assert len(fe.rt.futures) == len(futs)
    arrived = fe.server_poll()
    arrived += fe.server_poll()
    # nothing overwritten: every accepted rid arrived exactly once
    assert sorted(r.rid for r in arrived) == sorted(
        r.rid for r in reqs[:len(futs)])
    for f in futs:
        assert f.result(timeout=5.0)["queued"]
    # refused requests retry once credits return — no loss at the app layer
    retry = [r for r in reqs if r.rid not in {a.rid for a in arrived}]
    for r in retry:
        f = None
        for _ in range(20):                   # poll loop frees ring credits
            f = fe.submit(r)
            if f is not None:
                break
            fe.server_poll()
        assert f is not None, f"rid {r.rid} never admitted"
    fe.rt.drain(deadline=5.0)
    stats = fe.dispatcher.per_peer_stats()["server"]
    assert stats["timed_out"] == 0                    # seeded key, no .get
    assert stats["backpressure"] >= refused
    assert len(fe.rt.futures) == 0                    # all resolved


# ---------------------------------------------------------------------------
# single-host server


def test_host_server_completion_off_decode_path(params):
    """admit() means *running*; a request is done only when tick() returns
    it — and then its token count matches its budget exactly."""
    srv = Server(TINY, params, batch_slots=4, cache_len=32)
    reqs = _reqs(3, max_new=4)
    for r in reqs:
        assert srv.admit(r)
        assert len(r.out) == 1            # first (prefill) token only
    done = []
    for _ in range(20):
        _, fin = srv.tick()
        done += fin
        if not srv.active:
            break
    assert {r.rid for r in done} == {0, 1, 2}
    assert all(len(r.out) == 4 for r in done)
    # wave summary quotes THIS wave's delta, not the cumulative history
    line1 = srv.wave_summary()
    assert "admitted=3" in line1
    line2 = srv.wave_summary()
    assert "admitted=0" in line2 and "decoded=0" in line2


def test_host_server_scopes_once_per_admit_and_tick(params):
    """The serving step's layer scopes: prefill and install once per
    admit, decode dispatch and token wait once per tick."""
    from repro.obs import Obs

    obs = Obs("t", trace=True)
    srv = Server(TINY, params, batch_slots=4, cache_len=32, obs=obs)
    reqs = _reqs(2, max_new=4)
    for r in reqs:
        assert srv.admit(r)
    for _ in range(3):
        srv.tick()
    spans = obs.tracer.spans(cat="scope")
    count = {n: sum(s.name == n for s in spans) for n in (
        "repro.serve.prefill", "repro.serve.install",
        "repro.serve.decode_dispatch", "repro.serve.token_wait")}
    assert count == {"repro.serve.prefill": 2, "repro.serve.install": 2,
                     "repro.serve.decode_dispatch": 3,
                     "repro.serve.token_wait": 3}
    prefill = [s for s in spans if s.name == "repro.serve.prefill"]
    assert [s.args["n"] for s in prefill] == [len(r.prompt) for r in reqs]
    assert all(s.args["n"] == 2 for s in spans
               if s.name == "repro.serve.decode_dispatch")
    assert obs.tracer.open_count() == 0


# ---------------------------------------------------------------------------
# the disaggregated fabric


def test_fabric_matches_host_token_for_token(params):
    host = Server(TINY, params, batch_slots=8, cache_len=32)
    ref = {}
    pending = _reqs(6)
    while pending or host.active:
        while pending and host.admit(pending[0]):
            pending.pop(0)
        _, fin = host.tick()
        for r in fin:
            ref[r.rid] = list(r.out)

    fab = ServingFabric(TINY, params, n_prefill=2, n_decode=2,
                        batch_slots=8, cache_len=32)
    done = fab.run(_reqs(6))
    fab.drain()
    assert {rid: list(r.out) for rid, r in done.items()} == ref
    assert fab.buffered_installs() == 0               # every slab streamed
    assert fab.streams_landed() == 6


def test_fabric_negotiates_advertised_codec(params):
    """The decode peer's admission ack advertises its codecs; the prefill
    tier arms its per-peer wire codec from the ack, not a constructor."""
    fab = ServingFabric(TINY, params, n_prefill=1, n_decode=2,
                        batch_slots=4, cache_len=32,
                        decode_codecs=("rle", "raw"))
    fab.run(_reqs(3, max_new=3))
    pw = fab.prefill_workers[0]
    assert pw._negotiated == {"decode0": "rle", "decode1": "rle"}
    for d in ("decode0", "decode1"):
        assert pw.rt.dispatcher.peers[d].codec.id == WC.RLE


def test_fabric_quant8_negotiation_completes(params):
    """quant8-advertising decode tier: negotiation lands on the lossy
    codec and the fabric still serves every request (header chunks ship
    raw, so slab routing survives)."""
    fab = ServingFabric(TINY, params, n_prefill=1, n_decode=2,
                        batch_slots=4, cache_len=32,
                        decode_codecs=("quant8", "raw"))
    done = fab.run(_reqs(4, max_new=3))
    assert len(done) == 4
    assert fab.buffered_installs() == 0
    pw = fab.prefill_workers[0]
    assert set(pw._negotiated.values()) == {"quant8"}
