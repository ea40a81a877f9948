"""μVM interpreter kernel — the device-tier ifunc executor (Pallas/TPU).

A TPU core cannot receive machine code at runtime, so injected "code"
arrives as *data*: a μcode program (see ``core.codegen.OPS``) interpreted
by this fixed, pre-compiled kernel.  Registers are (128,128) f32 VMEM
tiles; ``matmul`` drives the MXU; the external table (``loade``) is the
device GOT — operands name model-resident tensors by slot, bound at launch.

Grid: one step per payload tile; the whole program runs per tile
(data-parallel μcode).  Instruction streams live in SMEM; register file is
VMEM scratch.  Dispatch is a flat run of one ``pl.when`` per opcode: a
20-way ``lax.switch`` nests 20 deep in Mosaic and overflows the stack of
its layout inference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend
from repro.core.codegen import OPS, UVM_REGS, UVM_TILE

T = UVM_TILE
R = UVM_REGS


def _vm_kernel(op_ref, dst_ref, a_ref, b_ref, imm_ref,  # SMEM instr stream
               payload_ref, ext_ref,                     # VMEM in
               out_ref,                                  # VMEM out
               regs_ref):                                # VMEM scratch [R,T,T]
    n_instr = op_ref.shape[0]
    n_ext = ext_ref.shape[0]

    # a tile starts from a zero register file and a zero output: an output
    # block no ``store`` reaches is garbage on the chip, not zero
    regs_ref[...] = jnp.zeros((R, T, T), jnp.float32)
    out_ref[...] = jnp.zeros((1, T, T), jnp.float32)

    def step(pc, carry):
        op = op_ref[pc]
        d = dst_ref[pc]
        a = a_ref[pc]
        b = b_ref[pc]
        imm = imm_ref[pc]

        def ra():
            return regs_ref[a]

        def rb():
            return regs_ref[b]

        # register-writing opcodes (halt is a nop, store is below)
        results = {
            "loadp": lambda: payload_ref[0],
            "loade": lambda: ext_ref[jnp.minimum(a, n_ext - 1)],
            "add": lambda: ra() + rb(),
            "sub": lambda: ra() - rb(),
            "mul": lambda: ra() * rb(),
            "fma": lambda: regs_ref[d] + ra() * rb(),
            "relu": lambda: jnp.maximum(ra(), 0.0),
            "gelu": lambda: jax.nn.gelu(ra()),
            "exp": lambda: jnp.exp(ra()),
            "scale": lambda: ra() * imm,
            "matmul": lambda: jnp.dot(ra(), rb(),
                                      precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32),
            "max": lambda: jnp.maximum(ra(), rb()),
            "copy": ra,
            "zero": lambda: jnp.zeros((T, T), jnp.float32),
            "tanh": lambda: jnp.tanh(ra()),
            "rsqrt": lambda: jax.lax.rsqrt(jnp.abs(ra()) + 1e-12),
            "addi": lambda: ra() + imm,
            "muli": lambda: ra() * imm,
        }
        for name, result in results.items():
            @pl.when(op == OPS[name])
            def _(result=result):
                regs_ref[d] = result()

        @pl.when(op == OPS["store"])
        def _():
            out_ref[0] = ra()
        return carry

    jax.lax.fori_loop(0, n_instr, step, 0)


def ifunc_vm(prog, payload_tiles, externals):
    """Execute μcode over payload tiles.  externals: [n_ext, T, T] f32."""
    payload = jnp.asarray(payload_tiles, jnp.float32)
    ext = jnp.asarray(externals, jnp.float32)
    if ext.ndim == 2:
        ext = ext[None]
    if ext.shape[0] == 0:
        ext = jnp.zeros((1, T, T), jnp.float32)
    assert payload.ndim == 3 and payload.shape[1:] == (T, T), payload.shape
    n_tiles, n_ext = payload.shape[0], ext.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _vm_kernel,
        grid=(n_tiles,),
        in_specs=[smem] * 5 + [
            pl.BlockSpec((1, T, T), lambda i: (i, 0, 0)),          # payload tile
            pl.BlockSpec((n_ext, T, T), lambda i: (0, 0, 0)),      # ext table
        ],
        out_specs=pl.BlockSpec((1, T, T), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, T, T), jnp.float32),
        scratch_shapes=[pltpu.VMEM((R, T, T), jnp.float32)],
        interpret=backend.pallas_interpret(),
        name="ifunc_vm",
    )(jnp.asarray(prog.opcode, jnp.int32), jnp.asarray(prog.dst, jnp.int32),
      jnp.asarray(prog.a, jnp.int32), jnp.asarray(prog.b, jnp.int32),
      jnp.asarray(prog.imm, jnp.float32), payload, ext)
