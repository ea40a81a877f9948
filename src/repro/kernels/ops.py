"""Public wrappers for the Pallas kernels.

Kernels run compiled on an accelerator and in interpret mode on the CPU
(``repro.backend.pallas_interpret``).  ``ssd_scan_op`` matches the
models/ssm.py chunk layout so the model stack can swap its XLA path for
the kernel on TPU.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.codegen import UVM_TILE, UvmProgram
from repro.kernels.ifunc_vm import ifunc_vm
from repro.kernels.ring_poll import ring_poll
from repro.kernels.ssd_scan import ssd_scan


def uvm_execute(prog: UvmProgram, payload_tiles, externals) -> np.ndarray:
    """Device-tier ifunc execution (called by core.api poll for UVM frames)."""
    if len(externals) != len(prog.symbols):
        raise ValueError(f"program needs {len(prog.symbols)} externals "
                         f"({prog.symbols}), got {len(externals)}")
    ext = (jnp.stack([jnp.asarray(e, jnp.float32) for e in externals])
           if len(externals) else jnp.zeros((0, UVM_TILE, UVM_TILE)))
    out = ifunc_vm(prog, payload_tiles, ext)
    return np.asarray(out)


def mailbox_poll(slots) -> np.ndarray:
    """Validate device mailbox slots -> status per slot."""
    return np.asarray(ring_poll(jnp.asarray(slots, jnp.uint32)))


def ssd_scan_op(x, la, Bm, Cm):
    """[BH,nc,Q,hd] chunked SSD (kernel path)."""
    return ssd_scan(x, la, Bm, Cm)
