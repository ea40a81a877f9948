"""The trace reduction: busy union, window cut, per-program and per-op
sums, idle gaps named by the host span over them; and the reading of a
profiler trace recorded here."""

import glob
import os

import pytest

from bench import trace_reduce as TR

MS = 1e6   # ns
VM = ('%sweep.3 = f32[512,128,128]{2,1,0:T(8,128)S(1)} custom-call(s32[5]{0} '
      '%copy-done.2), custom_call_target="tpu_custom_call"')
POLL = ('%sweep.2 = s32[64,1]{1,0:T(8,128)S(1)} custom-call(s32[64,131078]{1,0} '
        '%x), custom_call_target="tpu_custom_call"')


def _events(n_dev=1):
    """A small recorded shape: a 10 ms window, two programs with their ops
    on each device, one op hanging over the window's end, host spans."""
    ops, mods = {}, {}
    for d in range(n_dev):
        plane = f"/device:TPU:{d}"
        ops[plane] = [
            ("%fusion.1 = u32[4]{0} fusion(u32[4]{0} %a), kind=kLoop",
             1 * MS, 1 * MS),
            (VM, 3 * MS, 2 * MS),
            (POLL, 4.5 * MS, 1 * MS),                          # overlaps
            (VM, 9 * MS, 2 * MS),                              # 1 ms inside
            ("%fusion.9 = f32[2]{0} fusion(f32[2]{0} %b)", 20 * MS, 1 * MS),
        ]
        mods[plane] = [("jit_deposit(7)", 1 * MS, 1 * MS),
                       ("jit_sweep(8)", 3 * MS, 2.5 * MS),
                       ("jit_sweep(8)", 9 * MS, 2 * MS)]
    host = [("bench.window", 0.0, 10 * MS, {}),
            ("bench.submit", 0.0, 1 * MS, {"n": 4}),
            ("bench.progress", 5.5 * MS, 3.5 * MS, {}),
            ("bench.tick", 6 * MS, 1 * MS, {"n": 1})]
    return TR.Events(ops, mods, host)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_reduce_sums_within_the_window(n_dev):
    red = TR.reduce(_events(n_dev))
    assert red.window_s == pytest.approx(10e-3)
    assert red.n_devices == n_dev
    # busy: [1,2] + [3,5.5] + [9,10] = 4.5 ms per device
    assert red.busy_s == pytest.approx(4.5e-3)
    t, n = red.op_time(lambda op: "= f32[" in op and "custom-call" in op)
    assert t == pytest.approx(3e-3) and n == 2
    t, n = red.op_time(lambda op: "= s32[" in op)
    assert t == pytest.approx(1e-3) and n == 1
    t, n = red.program_time(("jit_sweep",))
    assert t == pytest.approx(3.5e-3) and n == 2
    t, n = red.program_time(("jit_deposit",))
    assert t == pytest.approx(1e-3) and n == 1
    assert red.program_time(("jit_other",)) == (0, 0)


def test_idle_gaps_are_named_by_the_host():
    red = TR.reduce(_events())
    # gaps: [0,1] submit, [2,3] none, [5.5,9] progress (tick is inside)
    assert red.idle_gaps[0] == ["bench.progress", pytest.approx(3.5e-3)]
    names = [g[0] for g in red.idle_gaps]
    assert names[1:] == ["bench.submit", "host (no span)"]
    assert red.top_ops(1)[0][0] == "jit_sweep/%sweep.3 custom-call"
    assert {n for n, _ in red.top_ops(3)} == {
        "jit_sweep/%sweep.3 custom-call", "jit_sweep/%sweep.2 custom-call",
        "jit_deposit/%fusion.1 fusion"}


def test_no_window_mark_is_an_error():
    ev = _events()
    ev.host[:] = [h for h in ev.host if h[0] != TR.WINDOW]
    with pytest.raises(ValueError, match="bench.window"):
        TR.reduce(ev)


def test_load_events_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.submit", n=5):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = TR.find_xplane(str(tmp_path))
    ev = TR.load_events(path)
    names = [h[0] for h in ev.host]
    assert names.count("bench.submit") == 3 and TR.WINDOW in names
    assert all(h[3].get("n") == 5 for h in ev.host if h[0] == "bench.submit")
    lo, hi = TR.window_bounds(ev)
    assert hi > lo
    red = TR.reduce(ev)           # no accelerator planes on the CPU
    assert red.window_s > 0 and red.busy_s == 0
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
