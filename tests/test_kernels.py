"""Per-kernel allclose vs ref.py oracles, sweeping shapes/dtypes/programs."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - optional dep (see requirements.txt)
    from _hypothesis_stub import given, settings, st

from repro.core.codegen import OPS, UVM_REGS, assemble
from repro.kernels import ops as K
from repro.kernels import ref as REF
from repro.kernels.ring_poll import HDR_WORDS, MAGIC, TRAILER

RNG = np.random.default_rng(42)


# --- ifunc_vm ---------------------------------------------------------------

PROGRAMS = {
    "affine_relu": (
        [("loadp", 0), ("loade", 1, 0), ("matmul", 2, 0, 1), ("loade", 3, 1),
         ("add", 2, 2, 3), ("relu", 2, 2), ("store", 0, 2)], ("W", "b")),
    "gelu_scale": (
        [("loadp", 0), ("gelu", 1, 0), ("scale", 1, 1, 0, 0.25), ("store", 0, 1)], ()),
    "double_matmul": (
        [("loadp", 0), ("loade", 1, 0), ("matmul", 2, 0, 1),
         ("matmul", 3, 2, 1), ("sub", 3, 3, 0), ("store", 0, 3)], ("W",)),
    "fma_chain": (
        [("loadp", 0), ("copy", 1, 0), ("fma", 1, 0, 0), ("tanh", 1, 1),
         ("addi", 1, 1, 0, 0.5), ("store", 0, 1)], ()),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
@pytest.mark.parametrize("n_tiles", [1, 3])
def test_ifunc_vm_programs(name, n_tiles):
    instrs, symbols = PROGRAMS[name]
    prog = assemble(instrs, symbols=symbols)
    pay = RNG.standard_normal((n_tiles, 128, 128)).astype(np.float32)
    ext = [RNG.standard_normal((128, 128)).astype(np.float32) * 0.1
           for _ in symbols]
    out = K.uvm_execute(prog, pay, ext)
    ref = REF.ifunc_vm_ref(prog, pay, np.stack(ext) if ext else np.zeros((0, 128, 128), np.float32))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


op_name = st.sampled_from([o for o in sorted(OPS) if o not in ("halt",)])


@given(st.lists(st.tuples(op_name, st.integers(0, UVM_REGS - 1),
                          st.integers(0, UVM_REGS - 1), st.integers(0, UVM_REGS - 1),
                          st.floats(-1.5, 1.5, allow_nan=False)),
                min_size=1, max_size=12))
@settings(max_examples=15, deadline=None)
def test_ifunc_vm_random_programs(instrs):
    instrs = [("loadp", 0)] + list(instrs) + [("store", 0, 1)]
    prog = assemble(instrs, symbols=("e0", "e1", "e2", "e3", "e4", "e5", "e6", "e7"))
    pay = RNG.standard_normal((2, 128, 128)).astype(np.float32) * 0.5
    ext = np.stack([RNG.standard_normal((128, 128)).astype(np.float32) * 0.1
                    for _ in range(8)])
    out = K.uvm_execute(prog, pay, list(ext))
    ref = REF.ifunc_vm_ref(prog, pay, ext)
    assert np.isfinite(ref).all() == np.isfinite(out).all()
    mask = np.isfinite(ref)
    np.testing.assert_allclose(out[mask], ref[mask], rtol=5e-4, atol=5e-4)


# --- ring_poll ---------------------------------------------------------------

@given(st.lists(st.tuples(st.sampled_from(["empty", "ok", "noTrailer", "corrupt",
                                           "tooLong"]),
                          st.integers(1, 20)), min_size=1, max_size=12))
@settings(max_examples=30, deadline=None)
def test_ring_poll_property(cases):
    W = 32
    slots = np.zeros((len(cases), W), np.uint32)
    for i, (kind, fw) in enumerate(cases):
        if kind == "empty":
            continue
        s = slots[i]
        fw2 = (W - HDR_WORDS) + 5 if kind == "tooLong" else fw
        s[0], s[1], s[2], s[3] = MAGIC, fw2, 3, 0x123
        s[4] = int(s[0]) ^ int(s[1]) ^ int(s[2]) ^ int(s[3])
        if kind == "corrupt":
            s[4] ^= 0x10
        if kind in ("ok",):
            s[HDR_WORDS + fw2] = TRAILER
    st_k = K.mailbox_poll(slots)
    st_r = REF.ring_poll_ref(slots)
    np.testing.assert_array_equal(st_k, st_r)


# --- ssd_scan ---------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 2, 128, 64, 64), (2, 4, 128, 64, 128),
                                   (3, 1, 256, 32, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_ssd_scan_shapes(shape, dtype):
    BH, nc, Q, hd, ds = shape
    x = RNG.standard_normal((BH, nc, Q, hd)).astype(dtype)
    la = -np.abs(RNG.standard_normal((BH, nc, Q))).astype(np.float32) * 0.2
    Bm = (RNG.standard_normal((BH, nc, Q, ds)) * 0.2).astype(dtype)
    Cm = (RNG.standard_normal((BH, nc, Q, ds)) * 0.2).astype(dtype)
    y = np.asarray(K.ssd_scan_op(x, la, Bm, Cm))
    yr = np.asarray(REF.ssd_scan_ref(x, la, Bm, Cm))
    np.testing.assert_allclose(y, yr, rtol=3e-4, atol=3e-4)


# --- flash attention ---------------------------------------------------------

def _ref_attn(q, k, v, scale, window=0):
    import jax.numpy as jnp
    import jax as _jax

    S = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(S)
    kpos = jnp.arange(S)
    m = qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(m[None], s, -1e30)
    p = _jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("shape", [(2, 256, 64, 0, 128, 128),
                                   (1, 512, 128, 256, 256, 128),
                                   (2, 256, 64, 64, 128, 64)])
def test_flash_attention_fwd_bwd(shape):
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attn import flash_attention

    BH, S, hd, window, bq, bk = shape
    q, k, v = (jnp.asarray(RNG.standard_normal((BH, S, hd)), jnp.float32)
               for _ in range(3))
    scale = 1.0 / np.sqrt(hd)
    o = flash_attention(q, k, v, scale, window, bq, bk)
    np.testing.assert_allclose(np.asarray(o), np.asarray(_ref_attn(q, k, v, scale, window)),
                               rtol=3e-5, atol=3e-5)
    g = jax.grad(lambda *a: flash_attention(*a, scale, window, bq, bk).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: _ref_attn(*a, scale, window).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_flash_model_path_matches_fused():
    """attn_impl='flash' (kernel) == 'fused' (XLA) through the model layer."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L
    from repro.models.config import ModelConfig

    cfg_f = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                        num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=64,
                        q_chunk=256, dtype="float32", param_dtype="float32",
                        attn_impl="fused")
    cfg_k = cfg_f.with_(attn_impl="flash")
    p = L.init_from_specs(L.attn_specs(cfg_f), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    yf = L.attention_seq(p, x, cfg_f)
    yk = L.attention_seq(p, x, cfg_k)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yf), rtol=5e-5, atol=5e-5)


def test_flash_hbm_accounting_sane():
    from repro.kernels.flash_attn import flash_hbm_bytes

    fwd = flash_hbm_bytes(1, 16, 4096, 128, train=False)
    trn = flash_hbm_bytes(1, 16, 4096, 128, train=True)
    score_f32 = 16 * 4096 * 4096 * 4
    assert fwd < score_f32, "kernel fwd must beat one f32 score materialization"
    assert trn > fwd


def test_ssd_kernel_full_model_equivalence():
    """cfg.ssd_impl='kernel' (Pallas) == 'xla' through the whole stack."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as Tr
    from repro.models.config import ModelConfig

    cfg_x = ModelConfig(name="t", family="ssm", num_layers=2, d_model=64,
                        num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=128,
                        block_pattern=("ssd",), ssm_state=16, ssm_head_dim=16,
                        ssm_chunk=8, dtype="float32", param_dtype="float32")
    cfg_k = cfg_x.with_(ssd_impl="kernel")
    p = Tr.init_params(cfg_x, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    lx, _, _ = Tr.forward(p, {"tokens": toks}, cfg_x, mode="train")
    lk, _, _ = Tr.forward(p, {"tokens": toks}, cfg_k, mode="train")
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx), rtol=2e-4, atol=2e-4)
    _, cx, _ = Tr.forward(p, {"tokens": toks}, cfg_x, mode="prefill")
    _, ck, _ = Tr.forward(p, {"tokens": toks}, cfg_k, mode="prefill")
    for k in cx:
        np.testing.assert_allclose(np.asarray(ck[k]), np.asarray(cx[k]),
                                   rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_path():
    """kernel == the models/ssm.py XLA chunked path on the same math."""
    import jax.numpy as jnp

    from repro.models import ssm as S
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="t", family="ssm", num_layers=1, d_model=64,
                      num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=64,
                      block_pattern=("ssd",), ssm_state=32, ssm_head_dim=16,
                      ssm_chunk=8, dtype="float32", param_dtype="float32")
    BH, nc, Q, hd, ds = 2, 4, 8, 16, 32
    x = RNG.standard_normal((BH, nc, Q, hd)).astype(np.float32)
    la = -np.abs(RNG.standard_normal((BH, nc, Q))).astype(np.float32) * 0.1
    Bm = (RNG.standard_normal((BH, nc, Q, ds)) * 0.3).astype(np.float32)
    Cm = (RNG.standard_normal((BH, nc, Q, ds)) * 0.3).astype(np.float32)
    y = np.asarray(K.ssd_scan_op(x, la, Bm, Cm))
    yr = np.asarray(REF.ssd_scan_ref(x, la, Bm, Cm))
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-4)
