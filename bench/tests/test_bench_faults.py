"""A run with the timed path broken underneath reads ``correct`` false.

Each test skips the look for a chip and drives the rest of a run
(``bench/run.py``'s ``execute``) on the CPU at a tiny size, with one fault
planted in the program once set-up is over: a step that returns its state
unchanged, half of the batch left out, an answer or a token altered where
it is produced.  The exchange between chips left out is planted in a
four-device run in a process of its own."""

import math
import os
import subprocess
import sys

import pytest

from bench import harness as H

ROOT = H.ROOT
PEAK = H.load_json(H.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
TINY_LANE = dict(payload_tiles=1, slots=4, outstanding=4, pool=4,
                 sample_every=1, trace_start_s=0.2, trace_seconds=0.3)
TINY_AGG = dict(payload_tiles=1, agg_k=4, slots=2, outstanding=8, pool=8,
                sample_every=1, trace_start_s=0.2, trace_seconds=0.3)
# the published widths, two layers: logits at their real scale
TINY_MODEL = dict(num_hidden_layers=2, decode_slots=4, cache_len=64)
TINY_CHAT = dict(clients=4, prompt_mix={"8": 0.5, "16": 0.5}, max_new=[4, 8],
                 block=4, blocks=10, ingest_slots=4, sample=4)


def _cell(name, traffic=None, config=None):
    spec = H.load_json(ROOT / "BENCHMARK.json")
    cell = H.find_cell(name, spec)
    cell.traffic.update(traffic or {})
    cell.config.update(config or {})
    return cell


def _run(cell, fault=None, monkeypatch=None, seconds=1.0, at_start=False,
         control=False):
    """One run of ``cell``; ``fault(monkeypatch)`` is planted when set-up
    ends, so the warm-up itself runs sound, or with ``at_start`` before
    the program's objects are built."""
    import jax

    sys.path.insert(0, str(ROOT / "bench"))
    import run as R

    if fault is not None and at_start:
        fault(monkeypatch)
    elif fault is not None:
        real = H.Run.setup_done

        def setup_done(self):
            fault(monkeypatch)
            return real(self)
        monkeypatch.setattr(H.Run, "setup_done", setup_done)
    for kind in ("lane", "serve"):
        monkeypatch.setattr(H.load_module("loops", kind), "DRAIN_S", 3.0)
    return R.execute(cell, 2**32 + 17, seconds, False, jax.devices()[:1],
                     control=control, peak=PEAK)


# -- lanes ---------------------------------------------------------------------


def _wrap_sweep(mp, edit):
    """Every sweep built from now on passes its outputs through ``edit``."""
    from repro.core import device_mailbox as DM
    from repro.transport.device_fabric import DeviceMeshMailbox

    def wrapped(mb_self, ctx, targs, budget=None):
        real_sweep = mb_self._sweep
        mb_self._sweep = lambda m, e: edit(real_sweep(m, e))
        try:
            return real_sweep_method(mb_self, ctx, targs, budget)
        finally:
            mb_self._sweep = real_sweep
    real_sweep_method = DeviceMeshMailbox.sweep
    mp.setattr(DeviceMeshMailbox, "sweep", wrapped)
    del DM


def _altered(mp):
    def edit(outs):
        *head, out, cleared = outs
        return (*head, out.at[..., 0, 0].add(1.0), cleared)
    _wrap_sweep(mp, edit)


def _one_sub_altered(mp):
    """Only the second sub-record of each container comes back altered."""
    def edit(outs):
        *head, out, cleared = outs
        return (*head, out.at[:, :, 1, ..., 0, 0].add(1.0), cleared)
    _wrap_sweep(mp, edit)


def _half_left_out(mp):
    import jax.numpy as jnp

    from repro.kernels.ring_poll import EMPTY

    def edit(outs):
        status = outs[0]
        odd = (jnp.arange(status.shape[1]) % 2 == 1)[None]
        return (jnp.where(odd, EMPTY, status), *outs[1:])
    _wrap_sweep(mp, edit)


def _state_unchanged(mp):
    from repro.transport.device_fabric import DeviceMeshMailbox

    def publish(self):                 # the deposit leaves the ring as it was
        self._staged = None
        self._deposited += self._staged_count
        self._staged_count = 0
    mp.setattr(DeviceMeshMailbox, "_publish", publish)


@pytest.mark.parametrize("name,traffic", [
    ("affine.512k.w64", TINY_LANE), ("affine.agg64k.w1024", TINY_AGG)],
    ids=["singleton", "aggregate"])
def test_lane_sound_run_is_correct(name, traffic, monkeypatch):
    cell = _cell(name, traffic)
    res = _run(cell, monkeypatch=monkeypatch)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _state_unchanged],
                         ids=["answer_altered", "half_left_out",
                              "state_unchanged"])
@pytest.mark.parametrize("name,traffic", [
    ("affine.512k.w64", TINY_LANE), ("affine.agg64k.w1024", TINY_AGG)],
    ids=["singleton", "aggregate"])
def test_lane_fault_reads_incorrect(name, traffic, fault, monkeypatch):
    res = _run(_cell(name, traffic), fault, monkeypatch)
    assert res["correct"] is False


@pytest.mark.parametrize("traffic", ["512k.w64", "512k.w64.x4",
                                     "agg64k.w1024"])
def test_lane_sample_reaches_every_slot_and_sub(traffic):
    """The sampling stride is prime to the ring's slots and to a
    container's sub-records, so a fault confined to some of them is seen."""
    tr = H.load_json(H.BENCH / "traffic" / f"{traffic}.json")
    positions = tr["slots"] * max(tr["agg_k"], 1)
    assert math.gcd(tr["sample_every"], positions) == 1


def test_lane_one_sub_fault_reads_incorrect(monkeypatch):
    """The aggregate cell's sampling at a tiny size (a stride prime to
    ``agg_k``, as 61 is to 64) catches a fault in one sub-position."""
    traffic = dict(TINY_AGG, sample_every=3, outstanding=16, slots=4)
    res = _run(_cell("affine.agg64k.w1024", traffic), _one_sub_altered,
               monkeypatch)
    assert res["correct"] is False


@pytest.mark.parametrize("name,traffic", [
    ("affine.512k.w64", TINY_LANE), ("affine.agg64k.w1024", TINY_AGG)],
    ids=["singleton", "aggregate"])
def test_lane_control_reads_incorrect(name, traffic, monkeypatch):
    """With the control's answers in the program's place, the harness's
    own comparison reads ``correct`` false."""
    res = _run(_cell(name, traffic), monkeypatch=monkeypatch, control=True)
    assert res["correct"] is False and res["failed"] == 0
    assert res["checks"]["max_abs_err"]["value"] > 1e-5


EXCHANGE = """
import os, sys, jax
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
sys.path.insert(0, {src!r})
import run as R
from bench import harness as H
from repro.core import device_mailbox as DM
cell = H.find_cell("affine.512k.w64.x4")
cell.traffic.update({traffic!r})
lane = H.load_module("loops", "lane")
lane.DRAIN_S = 3.0
peak = H.load_json(H.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
sound = R.execute(cell, 9, 1.0, False, jax.devices()[:4], peak=peak)
real = DM.make_deposit
def no_exchange(mesh, axis):
    dep = real(mesh, axis)
    return lambda mb, out, shift: dep(mb, out, shift=0)
DM.make_deposit = no_exchange
broken = R.execute(cell, 9, 1.0, False, jax.devices()[:4], peak=peak)
print("RESULT", sound["correct"], broken["correct"])
"""


def test_lane_exchange_left_out_reads_incorrect():
    code = EXCHANGE.format(bench=str(ROOT / "bench"), root=str(ROOT),
                           src=str(ROOT / "src"),
                           traffic=dict(TINY_LANE, outstanding=8))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_IFUNC_LIB_DIR=str(ROOT / "ifunc_libs"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    assert line[0].split()[1:] == ["True", "False"]


# -- serving -------------------------------------------------------------------


def _decode_fault(edit):
    """Every batcher built from now on decodes through ``edit`` (planted
    before the server is built, since the batcher binds its step then)."""
    def plant(mp):
        import jax

        from repro.serving import batcher as B
        from repro.train import serve as SRV

        def init(self, *a, **kw):
            real_init(self, *a, **kw)
            step = jax.jit(SRV.make_decode_step(self.cfg))
            self._decode = lambda p, c, t, pos: edit(c, *step(p, c, t, pos))
        real_init = B.ContinuousBatcher.__init__
        mp.setattr(B.ContinuousBatcher, "__init__", init)
    return plant


def _serve_state_unchanged(mp):
    _decode_fault(lambda old, new, logits: (old, logits))(mp)


def _serve_half_left_out(mp):
    def edit(old, new, logits):
        half = logits.shape[0] // 2
        return new, logits.at[half:].set(0.0)
    _decode_fault(edit)(mp)


def _serve_token_altered(mp):
    from repro.serving import batcher as B

    real_tick = B.ContinuousBatcher.tick

    def tick(self):
        emitted, finished = real_tick(self)
        for req in list(self.active.values()) + finished:
            if len(req.out) == 3:
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
        return emitted, finished
    mp.setattr(B.ContinuousBatcher, "tick", tick)


def _chat():
    return _cell("smollm360m.chat.c64", TINY_CHAT, TINY_MODEL)


def test_serve_sound_run_is_correct(monkeypatch):
    cell = _chat()
    res = _run(cell, monkeypatch=monkeypatch)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) >= {"output_tokens_per_s", "setup_s"}


def test_serve_control_takes_the_programs_place(monkeypatch):
    """With ``control``, the harness compares the int8 control's tokens in
    place of the served ones: each number checked is the control's, which
    lies above the program's.  (At this size the control's mean gap swings
    around the limit; at the cell's own size it read 2.5x the limit or more
    on every seed tried.)"""
    import ast

    res = _run(_chat(), monkeypatch=monkeypatch, control=True, seconds=3.0)
    notes = dict(n.split(": ", 1) for n in res["notes"]
                 if n.startswith(("program: ", "control: ")))
    prog, ctl = (ast.literal_eval(notes[k]) for k in ("program", "control"))
    assert res["checks"] and res["failed"] == 0
    for name, c in res["checks"].items():
        assert c["value"] == ctl[name] > prog[name]


@pytest.mark.parametrize("fault", [_serve_state_unchanged,
                                   _serve_half_left_out,
                                   _serve_token_altered],
                         ids=["state_unchanged", "half_left_out",
                              "token_altered"])
def test_serve_fault_reads_incorrect(fault, monkeypatch):
    res = _run(_chat(), fault, monkeypatch, at_start=True)
    assert res["correct"] is False
