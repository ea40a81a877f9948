"""A Mamba-2 + NoPE-attention hybrid (Granite 4.0-H's layer equations) on
the serving path, at a small size on the CPU: served logits against the
plain reference, the chunked prefill's final state against the token-by-
token recurrence, the decode state-step kernel against its XLA form, and
the disaggregated fabric's slab hand-off against the single-host server.

The tiny configuration keeps every mechanism of the published one: the
layer types alternate (mamba, attention) so the stack scans two periods
and runs one trailing Mamba layer (3 Mamba + 2 attention layers), the
muP multipliers are Granite's, and the chunk (16) is shorter than most
prompts, which are not multiples of it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import BENCH, load_json
from bench.reference import granite_hybrid as G
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import Request, Server, ServingFabric

GRANITE = load_json(BENCH / "configs" / "granite_4_0_h_micro.json")
TINY = dict(GRANITE, num_hidden_layers=5,
            layer_types=["mamba", "attention", "mamba", "attention", "mamba"],
            hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=512, mamba_n_heads=4,
            mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=16,
            serve_dtype="float32")

# The program runs in float32 here, so it differs from the float32
# reference only by the order of its sums: the chunked scan against the
# token-by-token recurrence, batched against single-row matmuls.  Logits
# are O(1e-2) (divided by 8), so 1e-6 is 0.01% of their scale.
ATOL = 1e-6


@pytest.fixture(scope="module")
def model():
    d = G.Dims.of(TINY)
    cfg = ModelConfig(**G.program_config(TINY))
    w = G.init_weights(d, 2**33 + 16, jnp.float32)
    p = G.program_params(d, dict(jax.tree.map(lambda a: a.copy(), w)))
    return d, cfg, w, p


def _ref_logits(d, w, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(G._forward(d, w, jnp.asarray(tokens, jnp.int32),
                                     G._linear_f32))


def _record_decode(batcher, into: dict):
    """Keep each decode step's logits by request id."""
    step = batcher._decode

    def recorded(params, cache, tokens, pos):
        who = {s: r.rid for s, r in batcher.active.items()}
        cache, logits = step(params, cache, tokens, pos)
        for s, rid in who.items():
            into.setdefault(rid, []).append(np.asarray(logits[s, -1]))
        return cache, logits

    batcher._decode = recorded


def test_pattern_and_sizes_follow_layer_types():
    cfg = ModelConfig(**G.program_config(GRANITE))
    assert cfg.block_pattern == ("ssd_mlp",) * 5 + ("attn",) + ("ssd_mlp",) * 4
    assert cfg.n_super == 4 and cfg.trailing == ()
    assert not cfg.use_rope
    assert cfg.param_counts()["total"] == 3_191_232_256
    tiny = ModelConfig(**G.program_config(TINY))
    assert tiny.block_pattern == ("ssd_mlp", "attn")
    assert tiny.trailing == ("ssd_mlp",)


def test_hybrid_server_matches_reference_at_every_served_position(model):
    """Slots join mid-wave and a retired slot is reused: every served
    token's logits, prefill's and each decode step's, match the
    reference's full forward pass over prompt + served tokens."""
    d, cfg, w, p = model
    rng = np.random.default_rng(7)
    lens = [37, 5, 16, 23, 40]                 # a reuse needs > 3 slots' worth
    reqs = [Request(i, rng.integers(0, d.vocab, n, dtype=np.int32), m)
            for i, (n, m) in enumerate(zip(lens, [6, 3, 5, 4, 4]))]
    with jax.default_matmul_precision("highest"):
        srv = Server(cfg, p, batch_slots=3, cache_len=64)
        first, logits = {}, {}
        prefill = srv._prefill

        def recorded_prefill(params, inputs):
            cache, last = prefill(params, inputs)
            first[len(first)] = np.asarray(last[0, -1])
            return cache, last

        srv._prefill = recorded_prefill
        _record_decode(srv.batcher, logits)
        pending, slots_used, done = list(reqs), {}, []
        for turn in range(40):
            if turn % 2 == 0 and pending and srv.admit(pending[0]):
                r = pending.pop(0)
                slots_used[r.rid] = next(s for s, q in srv.active.items()
                                         if q is r)
            _, fin = srv.tick()
            done += fin
            if not pending and not srv.active:
                break
    assert len(done) == len(reqs)
    assert len(set(slots_used.values())) < len(reqs)      # a slot was reused
    for r in reqs:
        assert len(r.out) == r.max_new
        ref = _ref_logits(d, w, np.concatenate([r.prompt, r.out]))
        P = len(r.prompt)
        got = np.stack([first[r.rid]] + logits[r.rid])
        np.testing.assert_allclose(got, ref[P - 1:P - 1 + r.max_new],
                                   atol=ATOL, rtol=0)
        assert r.out == list(np.argmax(ref[P - 1:P - 1 + r.max_new], -1))


def test_chunked_prefill_final_state_is_exact_at_any_length(model):
    """A 37-token prompt with chunk 16 (padded with dt = 0 to 48): the
    chunked prefill's outputs and final state and conv tail equal the
    token-by-token decode recurrence from a zero state."""
    d, cfg, _, p = model
    lp = {k[len("s0_"):]: v[0] for k, v in p.items() if k.startswith("s0_")}
    x = 0.5 * jax.random.normal(jax.random.key(3), (2, 37, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, cache = SSM.ssd_seq_cached(lp, x, cfg, want_cache=True)
        st = {k[len("s0_"):]: jnp.zeros(s.shape, s.dtype) for k, s in
              T.cache_shapes(cfg, 2, 1).items() if k.startswith("s0_")}
        ys = []
        for t in range(37):
            yt, st = SSM.ssd_decode(lp, x[:, t:t + 1], cfg, st, 0)
            ys.append(yt)
    # float32 chunked sums against one step at a time: 1e-5 of O(1) values
    np.testing.assert_allclose(np.asarray(cache["state"]),
                               np.asarray(st["state"][0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(cache["conv"]),
                                  np.asarray(st["conv"][0]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.concatenate(ys, 1)),
                               atol=1e-5, rtol=1e-5)


def test_ssd_step_kernel_matches_xla_step():
    """The Pallas state step (interpret mode) against its XLA form: the
    chosen layer of the stack advanced, every other layer untouched, and
    the outputs."""
    from repro.kernels.ssd_step import ssd_state_step

    L, Bt, nh, hd, ds = 3, 4, 8, 64, 128
    ks = jax.random.split(jax.random.key(5), 8)
    state = jax.random.normal(ks[0], (L, Bt, nh, hd, ds))
    x = jax.random.normal(ks[1], (Bt, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (Bt, nh)))
    B, C = (jax.random.normal(k, (Bt, ds)) for k in ks[3:5])
    A = -jnp.exp(jax.random.normal(ks[5], (nh,)))
    D = jax.random.normal(ks[6], (nh,))
    with jax.default_matmul_precision("highest"):
        want_y, want_s = SSM._state_step_xla(state, jnp.int32(1), x, dt, B, C,
                                             A, D)
        got_y, got_s = jax.jit(ssd_state_step)(state, jnp.int32(1), x, dt, B,
                                               C, A, D)
    # the same float32 products; the C dot sums 128 lanes in another order
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_s[::2]), np.asarray(state[::2]))


def test_install_splices_recurrent_rows_and_counts_their_bytes(model):
    """``install`` writes the slot's state and conv rows whole (a reused
    slot keeps nothing of its last sequence) and counts their bytes."""
    from repro.obs import Obs

    d, cfg, _, p = model
    obs = Obs("t", trace=True)
    srv = Server(cfg, p, batch_slots=2, cache_len=32, obs=obs)
    b = srv.batcher
    keys = T.recurrent_keys(cfg)
    assert set(keys) == {f"{s}_{n}" for s in ("s0", "t0")
                         for n in ("state", "conv")}
    b.cache = {k: (jnp.full_like(v, 7) if k in keys else v)
               for k, v in b.cache.items()}
    assert srv.admit(Request(0, np.arange(9, dtype=np.int32), 2))
    slot = next(iter(b.active))
    c1, _ = srv._prefill(p, {"tokens": np.arange(9, dtype=np.int32)[None]})
    for k in keys:
        bdim = 1 if k.startswith("s") else 0
        row = np.take(np.asarray(b.cache[k]), [slot], axis=bdim)
        np.testing.assert_array_equal(row, np.asarray(c1[k]).reshape(row.shape))
        other = np.take(np.asarray(b.cache[k]), [1 - slot], axis=bdim)
        assert (other == 7).all()
    one = T.cache_shapes(cfg, 1, 32, per_slot=True)
    want = sum(int(np.prod(one[k].shape)) * one[k].dtype.itemsize for k in keys)
    assert b.state_bytes == want
    snap = obs.snapshot()["counters"]
    assert snap["serve.host.state_bytes"] == want
    inst = [s for s in obs.tracer.spans(cat="scope")
            if s.name == "repro.serve.install"]
    assert [s.args["state_bytes"] for s in inst] == [want]


def test_hybrid_fabric_slab_handoff_matches_server(model):
    """Prefill peers ship the recurrent entries in the KV slab (float32,
    exact); the decode peers' logits equal the single-host server's."""
    d, cfg, _, p = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, d.vocab, n, dtype=np.int32)
               for n in (21, 9, 21, 30)]

    def reqs():
        return [Request(i, q, 4) for i, q in enumerate(prompts)]

    host_logits, fab_logits = {}, {}
    with jax.default_matmul_precision("highest"):
        srv = Server(cfg, p, batch_slots=4, cache_len=48)
        _record_decode(srv.batcher, host_logits)
        ref = {}
        pending = reqs()
        while pending or srv.active:
            while pending and srv.admit(pending[0]):
                pending.pop(0)
            for r in srv.tick()[1]:
                ref[r.rid] = list(r.out)
        fab = ServingFabric(cfg, p, n_prefill=1, n_decode=2, batch_slots=2,
                            cache_len=48, decode_codecs=("raw",))
        for dw in fab.decode_workers:
            _record_decode(dw.batcher, fab_logits)
        done = fab.run(reqs())
        fab.drain()
    assert {rid: list(r.out) for rid, r in done.items()} == ref
    assert fab.buffered_installs() == 0
    for rid in ref:
        # same-length prompts prefill as one batch on the fabric: float32
        # sums in another order
        np.testing.assert_allclose(np.stack(fab_logits[rid]),
                                   np.stack(host_logits[rid]),
                                   atol=ATOL, rtol=0)


def test_multipliers_at_their_defaults_trace_nothing():
    """The muP multipliers and NoPE switch at their defaults add no
    operation to a Llama-style decode step; set, each adds its own."""
    from repro.serving import TINY as DENSE
    from repro.train import serve as SRV

    def eqns(cfg):
        B, W = 2, 16
        jaxpr = jax.make_jaxpr(SRV.make_decode_step(cfg))(
            T.param_shapes(cfg), T.cache_shapes(cfg, B, W, per_slot=True),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
        out = []

        def walk(j):
            for e in j.eqns:
                out.append(e.primitive.name)
                for v in e.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                            walk(getattr(sub, "jaxpr", sub))
        walk(jaxpr.jaxpr)
        return out

    base = eqns(DENSE)
    same = eqns(DENSE.with_(embedding_multiplier=1.0, residual_multiplier=1.0,
                            logits_scaling=1.0, attention_multiplier=0.0,
                            use_rope=True))
    assert base == same
    mup = eqns(DENSE.with_(embedding_multiplier=12.0, residual_multiplier=0.22,
                           logits_scaling=8.0))
    # one scale of the embeddings, two residual branches per layer (one
    # scanned period of the one-kind pattern), one division of the logits
    assert mup.count("mul") == base.count("mul") + 1 + 2
    assert mup.count("div") == base.count("div") + 1
    nope = eqns(DENSE.with_(use_rope=False))
    assert base.count("sin") > 0 and nope.count("sin") == 0
