"""End-to-end system tests: the ifunc control plane driving a real training
loop (checkpoint triggers, LR hot-updates, probes), elastic restore, the
device-tier mailbox, and the multi-pod dry-run machinery (subprocess)."""

import os
import pathlib
import struct
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_control_plane_drives_training(tmp_path, lib_dir):
    """Controller injects set_lr + checkpoint + probe ifuncs into workers
    interleaved with train steps — behaviour changes with no restart."""
    from repro.core import Context
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.runtime.checkpoint import CheckpointManager
    from repro.runtime.controller import PodController, WorkerAgent
    from repro.train.optim import OptConfig
    from repro.train.step import make_train_step

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                      q_chunk=64, dtype="float32", param_dtype="float32")
    step = make_train_step(cfg, OptConfig(lr=1e-3, schedule="constant",
                                          warmup_steps=1))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": step.init_opt(params),
             "step": jnp.zeros((), jnp.int32)}
    cm = CheckpointManager(tmp_path / "ckpt")

    ckpts = []
    agent = WorkerAgent("w0", Context("w0", lib_dir=lib_dir))
    agent.hooks["checkpoint"] = lambda s: (cm.save(s, state), ckpts.append(s))
    agent.hooks["lr_scale"] = 1.0

    ctl = PodController(Context("ctl", lib_dir=lib_dir))
    ctl.attach(agent)

    jstep = jax.jit(step)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (4, 16), 0, 64),
             "labels": jax.random.randint(key, (4, 16), 0, 64)}
    for i in range(6):
        state, metrics = jstep(state, batch)
        if i == 1:
            ctl.inject("ctl_set_lr", struct.pack("<d", 0.5))
        if i == 3:
            ctl.inject("ctl_checkpoint", int(metrics["step"]).to_bytes(8, "little"))
        agent.poll()
    assert agent.hooks["lr_scale"] == 0.5
    assert ckpts == [4]
    assert cm.latest_step() == 4
    assert ctl.broadcast_until_acked("ctl_probe", b"ping")
    assert b"ping" in agent.hooks["acks"]

    # elastic restore onto fresh state (same mesh here; shardings arg unused)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = cm.restore(like)
    assert int(restored["step"]) == 4


def test_moe_shard_map_matches_dense_fallback():
    """Expert-parallel a2a/psum paths == the no-mesh dense reference."""
    from repro.models import moe as M
    from repro.models.config import ModelConfig
    from repro.models.layers import init_from_specs
    from repro.parallel.sharding import sharding_context

    cfg = ModelConfig(name="m", family="moe", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                      block_pattern=("attn_moe",), num_experts=4,
                      experts_per_token=2, moe_d_ff=16, capacity_factor=8.0,
                      dtype="float32", param_dtype="float32")
    p = init_from_specs(M.moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    y_ref, aux_ref = M._moe_dense_fallback(p, x, cfg)

    n = len(jax.devices())
    from repro.parallel.sharding import make_mesh

    mesh = make_mesh((1, n), ("data", "model"))
    with sharding_context(mesh):
        y_a2a, aux = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg))(p, x)
    # capacity_factor=8 -> no drops -> identical routing results
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)

    with sharding_context(mesh):
        y_psum, _ = jax.jit(lambda p, x: M.moe_ffn(p, x[:, :1], cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y_psum),
                               np.asarray(M._moe_dense_fallback(p, x[:, :1], cfg)[0]),
                               rtol=2e-4, atol=2e-4)


_MAILBOX_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.core.codegen import assemble
from repro.core.device_mailbox import (empty_mailbox, make_deposit, make_sweep,
                                       pack_word_frame)
from repro.kernels.ring_poll import READY, EMPTY

from repro.parallel.sharding import make_mesh

mesh = make_mesh((8,), ("model",))
prog = assemble([("loadp", 0), ("loade", 1, 0), ("add", 2, 0, 1), ("store", 0, 2)],
                symbols=("bias",))
T, NT, NS = 128, 1, 4
slot_words = 5 + NT*T*T + 1
rng = np.random.default_rng(0)
frames = np.zeros((8, NS, slot_words), np.uint32)
pay = rng.standard_normal((8, NT*T*T)).astype(np.float32)
for d in range(8):
    frames[d, 0] = pack_word_frame(pay[d], slot_words)
    frames[d, 1] = pack_word_frame(pay[d], slot_words, no_trailer=True)

mb = empty_mailbox(mesh, "model", NS, slot_words)
deposit = make_deposit(mesh, "model")
mb = deposit(mb, jnp.asarray(frames), shift=1)   # RDMA-put to right neighbor
ext = jnp.broadcast_to(jnp.ones((1, 1, T, T), jnp.float32) * 2.0, (8, 1, T, T))
sweep = make_sweep(mesh, "model", prog, NT)
status, out, cleared = sweep(mb, ext)
status = np.asarray(status)
assert (status[:, 0] == READY).all(), status
assert (status[:, 1] == 2).all(), status          # INFLIGHT (no trailer)
assert (status[:, 2:] == EMPTY).all(), status
out = np.asarray(out)
for d in range(8):
    src = (d - 1) % 8                              # neighbor's payload arrived
    np.testing.assert_allclose(out[d, 0].reshape(-1), pay[src] + 2.0, rtol=1e-5)
cleared = np.asarray(cleared)
assert (cleared[:, 0] == 0).all() and (cleared[:, 1, 0] != 0).all()
print("MAILBOX_OK")
"""


def test_device_mailbox_multidevice():
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src")
    r = subprocess.run([sys.executable, "-c", _MAILBOX_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MAILBOX_OK" in r.stdout, r.stdout + r.stderr


_SHIFT_FUTURES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core import Context, register_ifunc
from repro.core.codegen import deserialize_uvm
from repro.parallel.sharding import make_mesh
from repro.tasks import TaskRuntime
from repro.transport import Dispatcher, ProgressEngine
from repro.transport.device_fabric import DeviceMeshFabric

T = 128
mesh = make_mesh((4,), ("model",))
src = Context("src")
rt = TaskRuntime(src, Dispatcher(src, ProgressEngine(inflight_window="trailer")))
h = register_ifunc(src, "uvm_affine")
rng = np.random.default_rng(0)
W = (rng.standard_normal((T, T)) / np.sqrt(T)).astype(np.float32)
rt.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=1), None, n_slots=2,
            slot_size=128 << 10, prog=deserialize_uvm(h.lib.code),
            externals=np.broadcast_to(W, (4, 1, T, T)))
xs = rng.standard_normal((24, 1, T, T)).astype(np.float32)
futs = [rt.submit("tpu", h, x) for x in xs]
for x, f in zip(xs, futs):
    np.testing.assert_allclose(np.asarray(f.result()), np.maximum(x @ W, 0),
                               rtol=1e-4, atol=1e-5)
print("SHIFT_FUTURES_OK")
"""


def test_device_futures_shift_multidevice(lib_dir):
    """Deposits one shard to the right (shift=1) on a 4-device mesh: each
    frame executes on its neighbour, and the reply demux still hands every
    future the result of its own payload across three ring wraps."""
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src",
               REPRO_IFUNC_LIB_DIR=str(lib_dir))
    r = subprocess.run([sys.executable, "-c", _SHIFT_FUTURES_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SHIFT_FUTURES_OK" in r.stdout, r.stdout + r.stderr[-3000:]


_SHARDED_DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.models import transformer as T
from repro.parallel import sharding as SH
from repro.serving import TINY
from repro.train import serve as SRV

B, W = 4, 16
params = T.init_params(TINY, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
shapes = T.cache_shapes(TINY, B, W, per_slot=True)
cache = {k: jnp.full(s.shape, -1, jnp.int32) if k.endswith("slot_pos")
         else jnp.asarray(rng.standard_normal(s.shape), s.dtype)
         for k, s in shapes.items()}
toks = jnp.asarray(rng.integers(0, TINY.vocab_size, (B, 1)), jnp.int32)
pos = jnp.asarray([3, 9, 0, 15], jnp.int32)
ref_c, ref_l = jax.jit(SRV.make_decode_step(TINY))(params, cache, toks, pos)
mesh = SH.make_mesh((2, 2), ("data", "model"))
seq = SH.DEFAULT_RULES                     # the ring sharded over "model"
heads = seq.override(cache_seq=(), cache_kv_heads=("model",), act_heads=())
for rules in (seq, heads):
    with SH.sharding_context(mesh, rules):
        shd = SH.tree_shardings(T.cache_axes(TINY, B, W, per_slot=True),
                                shapes, mesh, rules)
        fn = jax.jit(SRV.make_decode_step(TINY),
                     in_shardings=(None, shd, None, None),
                     out_shardings=(shd, None))
        got_c, got_l = fn(params, jax.device_put(cache, shd), toks, pos)
    for k, want in ref_c.items():
        moved = np.argwhere(np.asarray(got_c[k]) != np.asarray(want))
        # upstream rounding may differ; only the written columns may
        assert all(i[-1] == int(pos[i[1]]) % W for i in moved), k
        if k.endswith("slot_pos"):
            assert len(moved) == 0, k
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(ref_l), atol=0.05)
print("SHARDED_DECODE_OK")
"""


def test_sharded_decode_writes_per_shard():
    """Under a mesh the decode step's column write runs per shard of the
    cache (the ring's positions, or its KV heads, split over "model"): a
    shard writes only the rows whose slot it holds, and the step agrees
    with the unsharded one."""
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_DECODE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_DECODE_OK" in r.stdout, r.stdout + r.stderr[-3000:]


_DRYRUN_SCRIPT = r"""
from repro.launch.dryrun import run_cell
rec = run_cell("mamba2_780m", "decode_32k", "pod", save_hlo=False, tag="test")
assert rec["status"] == "ok", rec
rec2 = run_cell("mamba2_780m", "decode_32k", "multipod", save_hlo=False, tag="test")
assert rec2["status"] == "ok", rec2
assert rec2["devices"] == 512 and rec["devices"] == 256
print("DRYRUN_OK")
"""


def test_dryrun_machinery_subprocess():
    """Lower+compile one real cell on the 16x16 AND 2x16x16 production
    meshes (512 fake devices) — proves the multi-pod sharding config."""
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src")
    r = subprocess.run([sys.executable, "-c", _DRYRUN_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_pipeline_parallel_schedule():
    """GPipe over a 1-D axis: outputs == sequential stage application."""
    from repro.parallel.pipeline import pipeline_apply
    from repro.parallel.sharding import make_mesh

    n = len(jax.devices())
    mesh = make_mesh((n,), ("pod",))
    ws = jnp.stack([jnp.eye(8) * (i + 1) for i in range(n)])

    def stage(w, x):
        return x @ w

    xs = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 8))
    out = pipeline_apply(stage, ws, xs, mesh, axis="pod")
    ref = xs
    for i in range(n):
        ref = jnp.einsum("mbd,de->mbe", ref, ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
