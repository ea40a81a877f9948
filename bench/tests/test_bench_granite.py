"""The Granite 4.0-H hybrid's reference and cell at small sizes on the CPU:
the plain reference against the program's forward pass, the int8 control
against the configuration's limit, a run of the cell through ``execute``
(sound, and with the control in the program's place), the kernel work the
roofline reader counts, and the window-1 aggregate lane cell."""

import ast
import sys

import numpy as np
import pytest

from bench import harness as H
from bench.reference import granite_hybrid as G

ROOT = H.ROOT
PEAK = H.load_json(H.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
GRANITE = H.load_json(H.BENCH / "configs" / "granite_4_0_h_micro.json")
TINY = dict(GRANITE, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"], hidden_size=64,
            intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=256, mamba_n_heads=2,
            mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=16)


def _program_logits(cfg, d, w, toks):
    import jax

    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    mc = ModelConfig(**G.program_config(dict(cfg, serve_dtype="float32")))
    p = G.program_params(d, dict(jax.tree.map(lambda a: a.copy(), w)))
    with jax.default_matmul_precision("highest"):
        return np.asarray(T.forward(p, {"tokens": toks[None]}, mc,
                                    mode="train")[0][0])


def test_granite_reference_matches_the_program_forward():
    """Float32 on both sides; 24 tokens with chunk 16 (the last chunk
    padded).  They differ only in the order of float32 sums (chunked scan
    against the token-by-token recurrence): 1e-6 of logits of O(1e-2)."""
    import jax
    import jax.numpy as jnp

    d = G.Dims.of(TINY)
    w = G.init_weights(d, 2**33 + 5, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 256, 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(G._forward(d, w, toks, G._linear_f32))
    got = _program_logits(TINY, d, w, toks)
    assert np.abs(ref).max() > 1e-3
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


def test_granite_weights_come_from_the_seed_with_mamba_inits():
    d = G.Dims.of(TINY)
    a, b = G.init_weights(d, 7), G.init_weights(d, 7)
    c = G.init_weights(d, 2**31 + 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["in_proj"], c["in_proj"])
    A = np.exp(np.asarray(a["A_log"], np.float32))
    assert 1.0 <= A.min() and A.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(a["dt_bias"], np.float32)))
    assert 0.9e-3 <= dt.min() and dt.max() <= 0.11
    assert (np.asarray(a["D"], np.float32) == 1).all()


def test_granite_control_fails_the_limit():
    """At the published widths with one whole period of the layer pattern
    (ten layers: nine Mamba, one attention), the int8 control's gaps over
    64 positions fail the configuration's limit.  The limit was set on the
    chip at the full 40 layers, where the control reads 1.3e-03; the gap
    grows with depth, and one period reads 4.8e-04 to 5.7e-04 here."""
    lt = GRANITE["layer_types"][:10]
    cfg = dict(GRANITE, num_hidden_layers=10, layer_types=lt)
    d = G.Dims.of(cfg)
    w = G.init_weights(d, 5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, d.vocab, 64, dtype=np.int32)
    served = rng.integers(0, d.vocab, 64, dtype=np.int32)
    _, ctl = G.logit_gaps(d, w, prompt, served, 128, control=True)
    values = G.compared([ctl], len(served))
    assert any(values[k] > lim for k, lim in GRANITE["limits"].items())


def test_ssd_step_work_is_memory_bound():
    d = G.Dims.of(GRANITE)
    fl, by = G.ssd_step_work(d, 1)
    assert by == 4 * (2 * 64 * 64 * 128 + 2 * 64 * 64 + 64 + 2 * 128)
    t, bound = H.roofline_s(fl * 64 * 36, by * 64 * 36, PEAK)
    assert bound == "memory" and t == pytest.approx(by * 64 * 36 / 819e9)
    # 64 slots x 36 layers: 9.66 GB a decode step, about 11.8 ms at 819 GB/s
    assert by * 64 * 36 == pytest.approx(9.66e9, rel=0.01)


def _run(cell, monkeypatch, seconds=1.0, control=False):
    import jax

    sys.path.insert(0, str(ROOT / "bench"))
    import run as R

    for kind in ("lane", "serve"):
        monkeypatch.setattr(H.load_module("loops", kind), "DRAIN_S", 3.0)
    return R.execute(cell, 2**32 + 23, seconds, False, jax.devices()[:1],
                     control=control, peak=PEAK)


def _granite_cell():
    """The cell at the published widths with one Mamba and one attention
    layer (logits at their real scale), 4 slots."""
    cell = H.find_cell("granite4hmicro.chat.c64")
    cell.config.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
                       decode_slots=4, cache_len=64)
    cell.traffic.update(clients=4, prompt_mix={"8": 0.5, "20": 0.5},
                        max_new=[4, 8], block=4, blocks=10, ingest_slots=4,
                        sample=4)
    return cell


def test_granite_cell_sound_run_and_control(monkeypatch):
    """The cell's loop drives the hybrid through ``IfuncFrontend`` ->
    ``Server`` and reads correct; with ``control`` every number checked is
    the control's, above the program's."""
    cell = _granite_cell()
    res = _run(cell, monkeypatch)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
    res = _run(_granite_cell(), monkeypatch, seconds=2.0, control=True)
    notes = dict(n.split(": ", 1) for n in res["notes"]
                 if n.startswith(("program: ", "control: ")))
    prog, ctl = (ast.literal_eval(notes[k]) for k in ("program", "control"))
    for name, c in res["checks"].items():
        assert c["value"] == ctl[name] > prog[name]


def test_window_one_lane_cell_is_correct(monkeypatch):
    """``affine.agg64k.w1``: one invocation in flight at a time through the
    aggregate lane, every answer returned and the sample correct."""
    cell = H.find_cell("affine.agg64k.w1")
    assert cell.traffic["outstanding"] == 1
    cell.traffic.update(agg_k=4, slots=2, pool=4, sample_every=1)
    res = _run(cell, monkeypatch)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"invocations_per_s.agg", "setup_s"}
