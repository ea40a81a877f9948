"""Tier-B demo: inject a μVM program into on-device mailboxes over the ICI,
through the unified transport layer.

Every device JAX sees is one shard of a ``DeviceMeshFabric``; a host-side
dispatcher sends ordinary ifunc frames (``uvm_affine``: y = relu(x @ W),
W bound from the target's external table — the device GOT).  The fabric
transcodes each wire frame into the device word-frame layout, one-sided-
deposits it into the *right neighbor's* ring buffer via collective_permute
(shift=1), and a single compiled sweep validates headers/trailers
(ring_poll kernel) and runs the injected program on every shard.

    PYTHONPATH=src python examples/device_injection.py

On a CPU host, ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
emulates eight shards; with one device the frames land in its own ring.
"""

import os
import pathlib

os.environ.setdefault("REPRO_IFUNC_LIB_DIR",
                      str(pathlib.Path(__file__).resolve().parents[1] / "ifunc_libs"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Context, ifunc_msg_create, register_ifunc
from repro.core.codegen import deserialize_uvm
from repro.transport import Dispatcher, ProgressEngine
from repro.transport.device_fabric import DeviceMeshFabric

from repro.parallel.sharding import make_mesh

T, NT, SHARDS = 128, 2, len(jax.devices())

mesh = make_mesh((SHARDS,), ("model",))
source = Context("host-source")
handle = register_ifunc(source, "uvm_affine")

rng = np.random.default_rng(0)
W = rng.standard_normal((T, T)).astype(np.float32) * 0.05

dispatcher = Dispatcher(source, ProgressEngine(inflight_window="trailer"))
dispatcher.add_peer(
    "tpu-mesh", DeviceMeshFabric(mesh, "model", shift=1), None,
    n_slots=2, slot_size=640 << 10, target_args={"results": []},
    prog=deserialize_uvm(handle.lib.code), n_tiles=NT,
    externals=jnp.broadcast_to(jnp.asarray(W)[None, None], (SHARDS, 1, T, T)))

payloads = rng.standard_normal((SHARDS, NT, T, T)).astype(np.float32)
for d in range(SHARDS):
    assert dispatcher.send("tpu-mesh", ifunc_msg_create(handle, payloads[d]))
print(f"posted {SHARDS} ifunc frames; flush deposits them via "
      f"collective_permute (ICI one-sided put, shift=1)")

n = dispatcher.drain()
print(f"swept {n} frames in one compiled ring_poll + ifunc_vm pass")

results = dispatcher.peers["tpu-mesh"].target_args["results"]
assert len(results) == SHARDS
for d in range(SHARDS):
    src = (d - 1) % SHARDS                     # neighbor's payload arrived
    ref = np.maximum(payloads[src] @ W, 0)
    np.testing.assert_allclose(np.asarray(results[d]), ref, rtol=1e-4, atol=1e-5)
dispatcher.print_stats()
print("all shards executed the injected program against their resident W — OK")
