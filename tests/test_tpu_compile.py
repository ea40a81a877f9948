"""Compile the device tier's kernels for a described TPU v5e, at real sizes.

Nothing runs: the TPU compiler, installed without a chip, compiles for a
topology that is described and not attached.  That catches what interpret
mode cannot (block tiling rules, VMEM/SMEM limits, Mosaic crashes) before
any chip time is spent.  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro import backend  # noqa: E402
from repro.core.codegen import OPS, assemble  # noqa: E402
from repro.kernels.ring_poll import HDR_WORDS  # noqa: E402

T = 128
N_TILES = 8                                  # 512 KiB frames
SLOTS = 64                                   # a 32 MiB singleton ring
AGG_K, AGG_SLOTS = 64, 16                    # a 64 MiB aggregate ring


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_for_tpu(monkeypatch):
    """Kernels lower for Mosaic, not the interpreter, and nothing lands in
    a persistent cache that could never be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _all_ops_program(n_ext: int):
    """Every opcode once, plus a load of the last external slot."""
    body = [(op, 1, 2, 3, 0.5) for op in OPS]
    return assemble([("loadp", 0), ("loade", 1, n_ext - 1), *body,
                     ("store", 0, 2)],
                    symbols=tuple(f"e{i}" for i in range(n_ext)))


def _compiled(fn, *args):
    # a fresh wrapper: jit's trace cache is keyed by function, and a trace
    # made here (Mosaic) must never serve a CPU caller of ``fn``
    c = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text(), "no Mosaic kernel in the program"
    return c


def test_ring_poll_compiles(one_chip):
    from repro.kernels.ring_poll import ring_poll

    slot_words = HDR_WORDS + N_TILES * T * T + 1
    _compiled(ring_poll, jax.ShapeDtypeStruct((SLOTS, slot_words), jnp.uint32,
                                              sharding=one_chip))


def test_agg_ring_poll_compiles(one_chip):
    from repro.kernels.agg_poll import agg_ring_poll

    u32 = jnp.uint32
    _compiled(agg_ring_poll,
              jax.ShapeDtypeStruct((AGG_SLOTS, HDR_WORDS + 2 * AGG_K), u32,
                                   sharding=one_chip),
              jax.ShapeDtypeStruct((AGG_SLOTS, 1), u32, sharding=one_chip),
              jax.ShapeDtypeStruct((1,), u32, sharding=one_chip))


def test_ifunc_vm_compiles(one_chip):
    """512 tiles, 8 externals, all 20 opcodes: the flat per-opcode dispatch
    compiles where a 20-way lax.switch crashed Mosaic's layout pass."""
    from repro.kernels.ifunc_vm import ifunc_vm

    prog = _all_ops_program(8)
    c = _compiled(lambda p, e: ifunc_vm(prog, p, e),
                  jax.ShapeDtypeStruct((512, T, T), jnp.float32,
                                       sharding=one_chip),
                  jax.ShapeDtypeStruct((8, T, T), jnp.float32,
                                       sharding=one_chip))
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("n_chips,agg_k", [(1, 0), (1, AGG_K), (4, 0),
                                           (4, AGG_K)])
def test_sweep_compiles(topo, n_chips, agg_k):
    """The jitted deposit + sweep of a device lane, singleton or aggregate,
    on one chip and across the 2x2 mesh (where the deposit is a
    collective-permute to the right neighbour)."""
    from repro.core.device_mailbox import (make_agg_sweep, make_deposit,
                                           make_sweep)

    mesh = Mesh(np.array(topo.devices[:n_chips]), ("model",))
    rows = NamedSharding(mesh, P("model"))
    prog = assemble([("loadp", 0), ("loade", 1, 0), ("matmul", 2, 0, 1),
                     ("relu", 2, 2), ("store", 0, 2)], symbols=("W",))
    if agg_k:
        slots, n_tiles = AGG_SLOTS, 1
        slot_words = HDR_WORDS + 2 * agg_k + agg_k * T * T + 1
        sweep = make_agg_sweep(mesh, "model", prog, agg_k, n_tiles,
                               bound_hash=0xBEEF)
    else:
        slots, n_tiles = SLOTS, N_TILES
        slot_words = HDR_WORDS + n_tiles * T * T + 1
        sweep = make_sweep(mesh, "model", prog, n_tiles)
    ring = jax.ShapeDtypeStruct((n_chips, slots, slot_words), jnp.uint32,
                                sharding=rows)
    ext = jax.ShapeDtypeStruct((n_chips, 1, T, T), jnp.float32, sharding=rows)
    c = sweep.lower(ring, ext).compile()
    assert "tpu_custom_call" in c.as_text()
    _kernels_keep_their_names(c.as_text(), agg_k)
    dep = make_deposit(mesh, "model").lower(ring, ring, shift=1).compile()
    if n_chips > 1:
        assert "collective-permute" in dep.as_text()


def _kernels_keep_their_names(text: str, agg_k: int):
    """The sweep's kernels carry their own names into the compiled module
    (so a trace's breakdown names them), and the roofline readers, which
    find them by HLO signature, still match each one and nothing else."""
    from bench.harness import load_module

    uvm = load_module("metrics", "uvm_roofline.lane").KERNEL
    poll = load_module("metrics", "poll_roofline.lane").KERNEL
    calls = {}
    for line in text.splitlines():
        op = line.strip().removeprefix("ROOT ")
        if "tpu_custom_call" in op:
            calls[op.split(" = ")[0].lstrip("%").split(".")[0]] = op
    poll_name = "agg_ring_poll" if agg_k else "ring_poll"
    assert set(calls) == {"ifunc_vm", poll_name}
    assert uvm.match(calls["ifunc_vm"]) and not poll.match(calls["ifunc_vm"])
    assert poll.match(calls[poll_name]) and not uvm.match(calls[poll_name])
    assert "/uvm/ifunc_vm/" in calls["ifunc_vm"]
    assert f"/poll/{poll_name}/" in calls[poll_name]


_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}


def test_decode_step_writes_cache_in_place(one_chip):
    """SmolLM-360M's donated decode step at published widths, 64 slots x
    2048: the stacked caches are the layer loop's carry, so the program
    needs less scratch than one layer's K, every cache output aliases its
    donated input, and no copy of the cache is left in it: a cache passed
    through the loop as its scan's xs/ys is relayouted per layer and copied
    whole after it, each copy one layer's K/V or more.  Only results with
    the ring's width as a dimension count (the logits' embedding table is
    copied too, and is no cache)."""
    import json
    import re

    from bench.harness import BENCH
    from bench.reference.llama import program_config
    from repro.models import transformer as TR
    from repro.models.config import ModelConfig
    from repro.train import serve as SRV

    conf = json.loads((BENCH / "configs" / "smollm_360m.json").read_text())
    cfg = ModelConfig(**program_config(conf))
    B, W = conf["decode_slots"], conf["cache_len"]

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(TR.param_shapes(cfg))
    cache = placed(TR.cache_shapes(cfg, B, W, per_slot=True))
    c = jax.jit(SRV.make_decode_step(cfg), donate_argnums=1).lower(
        params, cache,
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    layer_k = B * cfg.num_kv_heads * cfg.head_dim * W * 2
    assert layer_k == 83_886_080
    assert c.memory_analysis().temp_size_in_bytes < layer_k

    text = c.as_text()
    head = text.splitlines()[0]
    aliased = dict((int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head))
    n_params = len(jax.tree.leaves(params))
    assert all(aliased.get(o) == n_params + o
               for o in range(len(jax.tree.leaves(cache)))), head[:300]

    copies = []
    for dt, dims in re.findall(
            r"%copy[.\d]* = (\w+)\[([\d,]*)\]\S* copy\(", text):
        shape = [int(d) for d in dims.split(",") if d]
        if W in shape and _BYTES[dt] * int(np.prod(shape)) >= layer_k:
            copies.append(f"{dt}{shape}")
    assert not copies, f"cache-sized copies left: {copies}"


def test_granite_decode_step_updates_state_in_place(one_chip):
    """granite-4.0-h-micro's donated decode step at published widths, 64
    slots x 2048: every Mamba layer's state step is the ``ssd_step`` kernel
    on the stacked float32 state (one pattern slot's stack of 4 layers is
    537 MB), which stays the loop's carry: every cache output aliases its
    donated input, no temp buffer is as large as one slot's stack, and no
    copy of one is left.  The kernel keeps its name, by which the roofline
    reader finds it."""
    import json
    import re

    from bench.harness import BENCH, load_module
    from bench.reference.granite_hybrid import program_config
    from repro.models import transformer as TR
    from repro.models.config import ModelConfig
    from repro.train import serve as SRV

    conf = json.loads((BENCH / "configs" /
                       "granite_4_0_h_micro.json").read_text())
    cfg = ModelConfig(**program_config(conf))
    B, W = conf["decode_slots"], conf["cache_len"]

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(TR.param_shapes(cfg))
    cache = placed(TR.cache_shapes(cfg, B, W, per_slot=True))
    c = jax.jit(SRV.make_decode_step(cfg), donate_argnums=1).lower(
        params, cache,
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    stack = cache["s0_state"]
    stack_bytes = int(np.prod(stack.shape)) * 4
    assert stack_bytes == 536_870_912
    assert c.memory_analysis().temp_size_in_bytes < stack_bytes

    text = c.as_text()
    head = text.splitlines()[0]
    aliased = dict((int(o), int(i)) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head))
    n_params = len(jax.tree.leaves(params))
    assert all(aliased.get(o) == n_params + o
               for o in range(len(jax.tree.leaves(cache)))), head[:300]

    copies = []
    for dt, dims in re.findall(
            r"%copy[.\d]* = (\w+)\[([\d,]*)\]\S* copy\(", text):
        shape = [int(d) for d in dims.split(",") if d]
        if _BYTES[dt] * int(np.prod(shape)) >= stack_bytes // 4:
            copies.append(f"{dt}{shape}")
    assert not copies, f"state-sized copies left: {copies}"

    kernel = load_module("metrics", "ssd_step_ms.hybrid").KERNEL
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    names = {op.split(" = ")[0].lstrip("%").split(".")[0] for op in calls}
    assert names == {"ssd_step", "kv_column_write"}
    ssd = [op for op in calls if kernel.match(op)]
    assert ssd and all(op.startswith("%ssd_step") for op in ssd)
