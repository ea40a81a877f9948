"""Code serialization & linking (GOT analogue) + μVM assembler round-trip."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - optional dep (see requirements.txt)
    from _hypothesis_stub import given, settings, st

from repro.core import codegen as CG


# --- PYBC ------------------------------------------------------------------

def _helper(x):
    return x * 2


_CONST = 7


def _main_with_deps(payload, payload_size, target_args):
    target_args["out"] = _helper(payload_size) + _CONST + external_fn(1)  # noqa: F821


def test_pybc_bundles_locals_and_links_symbols():
    code = CG.serialize_pybc(_main_with_deps)
    space = CG.SymbolSpace({"external_fn": lambda v: v + 10})
    fn = CG.link_pybc(code, space)
    t = {}
    fn(b"1234", 4, t)
    assert t["out"] == 8 + 7 + 11


def test_pybc_unresolved_symbol():
    code = CG.serialize_pybc(_main_with_deps)
    with pytest.raises(CG.LinkError):
        CG.link_pybc(code, CG.SymbolSpace({}))


def test_pybc_magic_mismatch():
    code = bytearray(CG.serialize_pybc(_helper))
    # corrupt the interpreter magic inside the json meta
    idx = code.find(b'"magic"')
    code[idx + 12] ^= 0x01
    with pytest.raises(CG.CodeVerifyError):
        CG.link_pybc(bytes(code), CG.SymbolSpace())


def test_pybc_hmac():
    code = CG.serialize_pybc(_helper, hmac_key=b"secret")
    CG.link_pybc(code, CG.SymbolSpace(), hmac_key=b"secret")
    with pytest.raises(CG.CodeVerifyError):
        CG.link_pybc(code, CG.SymbolSpace(), hmac_key=b"other")
    unsigned = CG.serialize_pybc(_helper)
    with pytest.raises(CG.CodeVerifyError):
        CG.link_pybc(unsigned, CG.SymbolSpace(), hmac_key=b"secret")


def test_pybc_closure_rejected():
    y = 3

    def closure_fn(a):
        return a + y

    with pytest.raises(ValueError):
        CG.serialize_pybc(closure_fn)


# --- UVM -------------------------------------------------------------------

ops_strategy = st.sampled_from(sorted(CG.OPS))


@given(st.lists(st.tuples(ops_strategy,
                          st.integers(0, CG.UVM_REGS - 1),
                          st.integers(0, CG.UVM_REGS - 1),
                          st.integers(0, CG.UVM_REGS - 1),
                          st.floats(-2, 2, allow_nan=False)),
                min_size=1, max_size=24),
       st.lists(st.sampled_from(["W", "b", "t0", "t1"]), max_size=3, unique=True))
@settings(max_examples=40, deadline=None)
def test_uvm_serialize_roundtrip(instrs, symbols):
    prog = CG.assemble(list(instrs), symbols=tuple(symbols))
    blob = CG.serialize_uvm(prog)
    back = CG.deserialize_uvm(blob)
    np.testing.assert_array_equal(prog.opcode, back.opcode)
    np.testing.assert_array_equal(prog.dst, back.dst)
    np.testing.assert_array_equal(prog.a, back.a)
    np.testing.assert_array_equal(prog.b, back.b)
    np.testing.assert_allclose(prog.imm, back.imm)
    assert prog.symbols == back.symbols and prog.n_ext == back.n_ext


def test_uvm_bad_magic():
    with pytest.raises(CG.CodeVerifyError):
        CG.deserialize_uvm(b"\0" * 64)


@pytest.mark.parametrize("field,value", [("opcode", CG.N_OPS), ("dst", -1),
                                         ("a", CG.UVM_REGS), ("b", 99)])
def test_uvm_out_of_range_rejected(field, value):
    """Words the device kernel would index with unchecked are refused when
    the program arrives."""
    prog = CG.assemble([("loadp", 0), ("add", 1, 0, 0), ("store", 0, 1)])
    getattr(prog, field)[1] = value
    with pytest.raises(CG.CodeVerifyError, match="out of range"):
        CG.deserialize_uvm(CG.serialize_uvm(prog))


# --- HLO -------------------------------------------------------------------

def test_hlo_export_roundtrip():
    import jax
    import jax.numpy as jnp

    def f(x):
        return (x.astype(jnp.float32) * 2 + 1).sum()

    spec = (jax.ShapeDtypeStruct((16,), jnp.uint8),)
    code = CG.serialize_hlo(f, spec)
    call = CG.link_hlo(code)
    out = call(np.arange(16, dtype=np.uint8))
    assert float(out[0] if isinstance(out, (list, tuple)) else out) == float(np.arange(16).sum() * 2 + 16)
