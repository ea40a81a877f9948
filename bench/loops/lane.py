"""Closed-loop invocations of a μVM ifunc on a device lane.

A ``DeviceMeshFabric`` lane runs the configuration's ifunc (shipped once,
then invoked by digest) through ``TaskRuntime``.  The loop keeps
``outstanding`` invocations in flight: as results come back it submits as
many new ones, with ``TaskRuntime.submit`` on a singleton lane and with
``TaskRuntime.submit_many`` on an aggregate lane (``agg_k`` > 0, the
dispatcher coalescing up to ``agg_k`` invocations per container).

Traffic keys: ``payload_tiles`` (128x128 f32 tiles per invocation),
``agg_k``, ``slots`` (ring slots per chip), ``outstanding``, ``shift``
(a frame staged on chip s runs on chip s+shift), ``pool`` (distinct
payloads drawn from the seed, used in turn), ``sample_every`` (one
answer in so many is kept and compared), ``trace_start_s`` and
``trace_seconds`` (the part of the window a traced run profiles).

Every answer is checked for an error; the sampled ones, drawn from the
seed, are compared after the window with a float64 numpy relu(x @ W);
with ``run.control`` the control's answers for the same payloads take the
program's place in that comparison.  ``sample_every`` is prime to the
container size and the ring's slot count, so the sample covers every
sub-position of a container and every slot.
"""

from __future__ import annotations

import time

import numpy as np

from bench.harness import Check, Outcome, Run, percentile
from bench.reference import uvm_affine as REF

DRAIN_S = 60.0          # how long past the window an answer may still come


def _setup(run: Run):
    from repro.core import Context, register_ifunc
    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh
    from repro.tasks import TaskRuntime
    from repro.transport import Dispatcher, ProgressEngine
    from repro.transport.device_fabric import DeviceMeshFabric

    cfg, tr = run.cell.config, run.cell.traffic
    T = cfg["tile"]
    n_tiles, agg_k = tr["payload_tiles"], tr["agg_k"]
    devs = run.devices
    rng = np.random.default_rng(run.seed)
    w = (rng.standard_normal((T, T)) / np.sqrt(T)).astype(np.float32)
    pool = rng.standard_normal((tr["pool"], n_tiles, T, T), dtype=np.float32)

    mesh = make_mesh((len(devs),), ("model",), devices=devs)
    src = Context("bench")
    h = register_ifunc(src, cfg["ifunc"])
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer"))
    tile_bytes = n_tiles * T * T * 4
    kw = dict(n_slots=tr["slots"], prog=deserialize_uvm(h.lib.code),
              externals=np.broadcast_to(w, (len(devs), 1, T, T)))
    if agg_k:
        d.set_coalescing(True, max_subs=agg_k, max_sub_bytes=tile_bytes)
        kw.update(slot_size=agg_k * tile_bytes + (1 << 20), agg_k=agg_k,
                  n_tiles=n_tiles, prog_name=h.lib.name)
    else:
        kw.update(slot_size=tile_bytes + (64 << 10), n_tiles=n_tiles)
    rt = TaskRuntime(src, d, default_timeout=DRAIN_S)
    fabric = DeviceMeshFabric(mesh, "model", shift=tr["shift"])
    peer = rt.add_peer("tpu", fabric, None, **kw)
    return rt, peer, h, w, pool


class _Loop:
    """The closed loop's state: what is in flight and what came back."""

    def __init__(self, run: Run, rt, peer, h, pool):
        tr = run.cell.traffic
        self.run, self.rt, self.peer, self.h, self.pool = run, rt, peer, h, pool
        self.window = tr["outstanding"]
        self.batch = tr["agg_k"]
        self.every = tr["sample_every"]
        self.offset = int(np.random.default_rng([run.seed, 1]).integers(
            self.every))
        self.i = 0                       # invocations submitted
        self.inflight = 0
        self.done: list = []             # (index, t_submit, future)
        self.kept: dict[int, np.ndarray] = {}
        self.errors: list = []

    def _on_done(self, idx: int, t_sub: float):
        def cb(fut):
            self.inflight -= 1
            self.done.append((idx, t_sub, fut))
            exc = fut.exception()
            if exc is not None:
                self.errors.append((idx, repr(exc)))
            elif idx % self.every == self.offset:
                self.kept[idx] = np.array(fut.result(), np.float32, copy=True)
        return cb

    def fill(self) -> int:
        """Submit until ``outstanding`` invocations are in flight (or the
        lane has no credits left); returns how many were submitted."""
        want = self.window - self.inflight
        if want <= 0:
            return 0
        rt, spans, P = self.rt, self.run.spans, len(self.pool)
        if self.batch:
            want = min(want, self.peer.credits * self.batch)
            if want <= 0:
                return 0
            idx = range(self.i, self.i + want)
            t = time.monotonic()
            with spans("bench.submit", want):
                futs = rt.submit_many("tpu", self.h,
                                      [self.pool[j % P] for j in idx])
            for j, f in zip(idx, futs):
                self.inflight += 1
                f.add_done_callback(self._on_done(j, t))
            self.i += want
            return want
        n = 0
        while n < want:
            t = time.monotonic()
            with spans("bench.submit", 1):
                f = rt.submit("tpu", self.h, self.pool[self.i % P],
                              wait_credits=False)
            if f is None:
                break
            self.inflight += 1
            f.add_done_callback(self._on_done(self.i, t))
            self.i += 1
            n += 1
        return n

    def progress(self) -> None:
        with self.run.spans("bench.progress"):
            self.rt.progress()


def run(run: Run) -> Outcome:
    rt, peer, h, w, pool = _setup(run)
    lp = _Loop(run, rt, peer, h, pool)
    ch = peer.rings[0].channel
    tw = run.trace
    if tw is not None:
        tw.counter("resolved", lambda: len(lp.done))
        tw.counter("submitted", lambda: lp.i)
        tw.counter("flushes", lambda: ch.stats["flushes"])
        tw.counter("frames", lambda: ch.stats["puts"])

    # warm-up: one ring-full through the timed path compiles the lane's
    # deposit and sweep and confirms the ifunc in the target's link cache
    lp.fill()
    deadline = time.monotonic() + 1200.0
    while lp.inflight and time.monotonic() < deadline:
        lp.progress()
    if lp.inflight or lp.errors:
        raise RuntimeError(f"warm-up: {lp.inflight} in flight, errors "
                           f"{lp.errors[:3]}")
    lp.done.clear()
    lp.kept.clear()
    first = lp.i

    run.setup_done()
    t0 = time.monotonic()
    t_end = t0 + run.seconds
    while (now := time.monotonic()) < t_end:
        if tw is not None:
            tw.poll(now - t0)
        lp.fill()
        lp.progress()
    if tw is not None:
        tw.close()
    run.window_done()
    last = lp.i
    window_done = [r for r in lp.done if r[2].resolved_at <= t_end]
    limit = time.monotonic() + DRAIN_S
    while lp.inflight and time.monotonic() < limit:
        lp.progress()
    run.read_memory_peak()

    attempted = last - first
    came = {i for i, _, _ in lp.done}
    missing = attempted - len(came & set(range(first, last)))
    lat = [(f.resolved_at - ts) * 1e3 for i, ts, f in lp.done
           if first <= i < last and f.exception() is None]
    ok_in_window = sum(1 for i, _, f in window_done
                       if f.exception() is None and first <= i < last)
    metrics = {"invocations_per_s": ok_in_window / run.seconds}
    if lat:
        metrics["invocation_p99_ms"] = percentile(lat, 99)

    cfg = run.cell.config
    idx = sorted(i for i in lp.kept if first <= i < last)
    checks = []
    records = {"n_tiles": run.cell.traffic["payload_tiles"],
               "agg_k": run.cell.traffic["agg_k"], "tile": cfg["tile"]}
    if idx:
        P = len(pool)
        want = {j: REF.reference(pool[j], w) for j in {i % P for i in idx}}
        got = {"program": max(float(np.max(np.abs(lp.kept[i] - want[i % P])))
                              for i in idx)}
        if run.control:
            ctl = REF.control(pool, w)
            got["control"] = max(float(np.max(np.abs(ctl[i % P] - want[i % P])))
                                 for i in idx)
        checks.append(Check("max_abs_err",
                            got["control" if run.control else "program"],
                            cfg["limits"]["max_abs_err"]))
    notes = [f"{attempted} invocations in the window, {len(idx)} compared, "
             f"{len(lp.errors)} errors, {missing} never came"]
    if idx:
        notes += [f"{who} max_abs_err: {v!r}" for who, v in got.items()]
    return Outcome(attempted, len(lp.errors) + missing, metrics, checks,
                   records, notes)
