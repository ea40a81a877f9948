"""The plain references at a tiny size: the lane's relu(x @ W) and the
Llama-style forward pass, each against the program on the CPU, and each
control against its reference and the configuration's limit."""

import numpy as np
import pytest

from bench import harness as H
from bench.reference import llama as L
from bench.reference import uvm_affine as U

LANE = H.load_json(H.BENCH / "configs" / "uvm_affine_lane.json")
SMOL = H.load_json(H.BENCH / "configs" / "smollm_360m.json")
TINY = dict(SMOL, num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256)


def test_lane_reference_is_relu_of_the_product():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((3, 128, 128), dtype=np.float32)
    w = rng.standard_normal((128, 128), dtype=np.float32)
    want = np.stack([[[max(sum(float(x[t, i, k]) * float(w[k, j])
                                for k in range(128)), 0.0)
                       for j in (0, 5)] for i in (0, 77)] for t in range(3)])
    got = U.reference(x, w)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got[:, [0, 77]][:, :, [0, 5]], want,
                               rtol=1e-12, atol=1e-12)


def test_lane_control_fails_the_limit_and_f32_passes():
    """The control (three bf16 passes) errs past the limit on 8 tiles; the
    float32 product at the precision the program states stays under it."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    T = LANE["tile"]
    w = (rng.standard_normal((T, T)) / np.sqrt(T)).astype(np.float32)
    x = rng.standard_normal((8, T, T), dtype=np.float32)
    want = U.reference(x, w)
    limit = LANE["limits"]["max_abs_err"]
    ctl = float(np.max(np.abs(U.control(x, w) - want)))
    with jax.default_matmul_precision("highest"):
        f32 = np.maximum(np.asarray(jnp.asarray(x) @ jnp.asarray(w)), 0)
    assert ctl > limit
    assert float(np.max(np.abs(f32 - want))) < limit


def _program_config(cfg):
    from repro.models.config import ModelConfig

    return ModelConfig(**L.program_config(dict(cfg, serve_dtype="float32")))


def _program_logits(d, w, toks):
    import jax

    from repro.models import transformer as T

    cfg = _program_config(TINY)
    p = L.program_params(d, jax.tree.map(lambda a: a.copy(), w))
    with jax.default_matmul_precision("highest"):
        return np.asarray(T.forward(p, {"tokens": toks[None]}, cfg,
                                    mode="train")[0][0])


def test_llama_reference_matches_the_program_forward():
    import jax
    import jax.numpy as jnp

    d = L.Dims.of(TINY)
    w = L.init_weights(d, 2**33 + 5, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 256, 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(L._forward(d, w, toks, L._linear_f32))
    np.testing.assert_allclose(_program_logits(d, w, toks), ref,
                               rtol=2e-4, atol=2e-4)


def test_weights_come_from_the_seed():
    d = L.Dims.of(TINY)
    a, b = L.init_weights(d, 7), L.init_weights(d, 7)
    c = L.init_weights(d, 2**31 + 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert a["wq"].dtype.name == "bfloat16"


def test_served_float32_tokens_have_no_gap():
    """Prefill through ``Server.admit`` and decode through the per-slot
    cache in float32 give the reference's greedy tokens: gap 0."""
    import jax
    import jax.numpy as jnp

    from repro.serving import Request, Server

    d = L.Dims.of(TINY)
    cfg = _program_config(TINY)
    w = L.init_weights(d, 11, jnp.float32)
    p = L.program_params(d, jax.tree.map(lambda a: a.copy(), w))
    prompt = np.random.default_rng(4).integers(0, 256, 16, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        srv = Server(cfg, p, 2, 64)
        req = Request(1, prompt, 12)
        assert srv.admit(req)
        while srv.active:
            srv.tick()
    (total, widest, not_best), _ = L.logit_gaps(d, w, prompt, req.out, 64)
    assert len(req.out) == 12
    assert widest == pytest.approx(0.0, abs=1e-5)
    assert total == pytest.approx(0.0, abs=1e-4)
    assert not_best == 0


def test_llama_control_fails_the_limit():
    """At the published widths with two layers, the int8 control's gaps
    over 64 positions fail one of the configuration's limits."""
    cfg = dict(SMOL, num_hidden_layers=2)
    d = L.Dims.of(cfg)
    w = L.init_weights(d, 5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, d.vocab, 64, dtype=np.int32)
    served = rng.integers(0, d.vocab, 64, dtype=np.int32)
    _, ctl = L.logit_gaps(d, w, prompt, served, 128, control=True)
    values = L.compared([ctl], len(served))
    assert any(values[k] > lim for k, lim in SMOL["limits"].items())


def test_compared_numbers_of_a_sample():
    gaps = [(0.5, 0.25, 2.0), (1.5, 0.75, 1.0)]
    assert L.compared(gaps, 10) == {"mean_logit_gap": 0.2,
                                    "max_logit_gap": 0.75,
                                    "not_best_share": 0.3}
