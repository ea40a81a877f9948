"""Resident staging generations on the device lane.

A device lane stages each generation into one of two host buffers that it
keeps for its whole life and fills in turn, flush by flush.  A put writes
its word-frame straight into the row of the buffer being filled.  The
contracts under test:

* exactly once: a frame staged into a buffer is deposited with that
  generation only, never again when the buffer is filled later;
* a withheld trailer reads INFLIGHT in a row whose last frame was complete,
  until the flush completes it;
* a short container, in a row that last held a full one, reads SUB_EMPTY
  past its own sub-records and returns exactly its own values;
* a buffer taken for refilling right after it went up (its deposit waited
  for) leaves the swept results those of the generation it carried;
* the lane allocates two buffers however many flushes it makes, and a
  staged row equals the ``pack_*_word_frame`` oracle on every word the
  kernels read;
* an answer from a sweep that found few frames does not keep the sweep's
  whole readback alive.

Each runs on a singleton and an aggregate lane, on one chip (this process)
and on a 2-shard mesh with ``shift=1`` (one subprocess for all of those).
"""

import json
import os
import pathlib
import subprocess
import sys
import traceback

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import Context, register_ifunc  # noqa: E402
from repro.core import frame as F  # noqa: E402
from repro.core.api import Status, ifunc_msg_create  # noqa: E402
from repro.core.codegen import deserialize_uvm  # noqa: E402
from repro.core.device_mailbox import (pack_agg_word_frame,  # noqa: E402
                                       pack_word_frame)
from repro.kernels.ring_poll import HDR_WORDS, INFLIGHT, TRAILER  # noqa: E402
from repro.parallel.sharding import make_mesh  # noqa: E402
from repro.transport.device_fabric import DeviceMeshFabric  # noqa: E402

T = 128
K = 4
N_SLOTS = 4                     # per shard
KINDS = ("singleton", "agg")
MESHES = ("one_chip", "two_shards")
REPO = pathlib.Path(__file__).resolve().parents[1]


class Lane:
    """One device lane (mailbox + channel) running uvm_affine,
    relu(x @ W) with W = 0.5 I, on ``shards`` devices."""

    def __init__(self, kind: str, shards: int):
        self.kind = kind
        mesh = make_mesh((shards,), ("model",), devices=jax.devices()[:shards])
        self.h = register_ifunc(Context("src"), "uvm_affine")
        lib = self.h.lib
        self.name_hash = F.fletcher32(lib.name.encode()) & 0xFFFFFFFF
        agg_k = K if kind == "agg" else 0
        fab = DeviceMeshFabric(mesh, "model", shift=1 if shards > 1 else 0)
        w = np.eye(T, dtype=np.float32) * 0.5
        self.mb = fab.open_mailbox(
            None, N_SLOTS, 8 << 20, prog=deserialize_uvm(lib.code),
            externals=np.broadcast_to(w, (shards, 1, T, T)), agg_k=agg_k,
            prog_name=lib.name if agg_k else None)
        self.ch = fab.connect(None, self.mb)
        self.rng = np.random.default_rng(shards)
        self.staged: dict = {}          # coord -> payloads put, not swept

    def put(self, slot: int, n: int | None = None, *, single: bool = False,
            partial: bool = False) -> list:
        """Put a singleton (on the aggregate lane too with ``single``) or a
        container of ``n`` sub-records (all ``K`` by default) into
        ``slot``; returns its payloads."""
        lib = self.h.lib
        if self.kind == "singleton" or single:
            xs = [self.rng.standard_normal((1, T, T)).astype(np.float32)]
            msg = ifunc_msg_create(self.h, xs[0])
            data, size = msg.frame, msg.nbytes
        else:
            xs = [self.rng.standard_normal((1, T, T)).astype(np.float32)
                  for _ in range(K if n is None else n)]
            subs = [F.AggSub(lib.name, lib.kind, lib.code_digest, 0,
                             x.tobytes()) for x in xs]
            buf = bytearray(self.mb.slot_size)
            size = F.seal_agg_frame(buf, subs, kind=lib.kind)
            data = memoryview(buf)[:size]
        self.ch.put(data, slot, deliver_bytes=size - 3 if partial else None)
        self.staged[self.mb.slot_coords(slot)] = xs
        return xs

    def row(self, slot: int) -> np.ndarray:
        shard, idx = self.mb.slot_coords(slot)
        return self.mb._staged[shard, idx]

    def oracle(self, xs: list) -> np.ndarray:
        mb = self.mb
        if self.kind == "singleton":
            return pack_word_frame(xs[0], mb.slot_words, kind=int(self.h.lib.kind),
                                   name_hash=self.name_hash)
        return pack_agg_word_frame(xs, [self.name_hash] * len(xs), K,
                                   mb.body_words, mb.slot_words,
                                   kind=int(self.h.lib.kind))

    def read_words(self, row: np.ndarray, n: int) -> np.ndarray:
        """The words of a staged row the kernels read for a frame of ``n``
        sub-records: all of a singleton; a container's header, descriptor
        table, its ``n`` bodies and the trailer."""
        if self.kind == "singleton":
            return row
        bw = self.mb.body_words
        head = HDR_WORDS + 2 * K
        return np.concatenate([row[:head + n * bw], row[-1:]])

    def sweep(self) -> dict:
        """Sweep the ring; {staged coord: [values]} of every frame that
        came back READY, each coord at most once."""
        done, raised = self.mb.sweep(None, {})
        assert raised is None
        got: dict = {}
        for c in done:
            assert c.status == Status.OK, (c.status, c.coord)
            assert c.coord not in got, f"{c.coord} answered twice in one sweep"
            got[c.coord] = ([s.value for s in c.subs]
                            if self.kind == "agg" else [c.value])
        return got

    def check(self, got: dict) -> None:
        """``got`` answers exactly the frames staged since the last check,
        each with relu(0.5 x) for its own payloads."""
        assert set(got) == set(self.staged), (sorted(got), sorted(self.staged))
        for coord, xs in self.staged.items():
            vals = got[coord]
            assert len(vals) == len(xs), (coord, len(vals), len(xs))
            for v, x in zip(vals, xs):
                np.testing.assert_allclose(np.asarray(v),
                                           np.maximum(0.5 * x, 0),
                                           rtol=1e-5, atol=1e-6)
        self.staged.clear()

    def round(self, slots) -> None:
        for s in slots:
            self.put(s)
        self.ch.flush()
        self.check(self.sweep())


def exactly_once(lane: Lane) -> None:
    """Slots A and B, then C alone, then D alone into A and B's buffer,
    then A alone again: every frame READY once, nothing deposited twice."""
    a, b, c, d = range(4)
    for slots in ([a, b], [c], [d], [a]):
        lane.round(slots)
    assert lane.mb.consumed == 5
    status = lane.mb._run_sweep()[0]
    assert (status == 0).all(), status          # the ring is empty


def stale_trailer(lane: Lane) -> None:
    """A withheld trailer in a row whose last frame was complete."""
    mb = lane.mb
    lane.round([0])
    lane.round([1])                             # the other buffer
    lane.put(0, partial=True)                   # row of the first round
    assert lane.row(0)[-1] == 0
    status = np.asarray(mb._sweep(jax.device_put(mb._staged, mb._sharding),
                                  mb.externals)[0])
    assert status[mb.slot_coords(0)] == INFLIGHT, status
    lane.ch.flush()                             # completes the trailer
    lane.check(lane.sweep())
    assert lane.ch.stats["partial"] == 1


def short_container(lane: Lane) -> None:
    """A short container (``n`` = 2 sub-records, or with ``lane.single`` a
    singleton frame: one) staged into a row that last held a full one."""
    mb, single = lane.mb, lane.single
    full = lane.put(0)
    lane.ch.flush()
    lane.check(lane.sweep())
    lane.round([1])
    xs = lane.put(0, 2, single=single)
    n = len(xs)
    row = lane.row(0)
    head = HDR_WORDS + 2 * K
    assert row[1] == n
    assert not row[HDR_WORDS + 2 * n:head].any()  # descriptors past n
    bw = mb.body_words
    old = np.asarray(full[n], np.float32).reshape(-1).view(np.uint32)
    np.testing.assert_array_equal(row[head + n * bw:head + (n + 1) * bw], old)
    hashes = [mb.bound_hash if single else lane.name_hash] * n
    want = pack_agg_word_frame(xs, hashes, K, bw, mb.slot_words,
                               kind=int(lane.h.lib.kind))
    np.testing.assert_array_equal(lane.read_words(row, n),
                                  lane.read_words(want, n))
    lane.ch.flush()
    args: dict = {"results": []}
    done, _ = mb.sweep(None, args)
    assert [c.status for c in done] == [Status.OK]
    assert done[0].coord == mb.slot_coords(0) and len(done[0].subs) == n
    assert len(args["results"]) == n
    for v, x in zip(args["results"], xs):
        np.testing.assert_allclose(np.asarray(v), np.maximum(0.5 * x, 0),
                                   rtol=1e-5, atol=1e-6)
    lane.staged.clear()


@jax.jit
def _held(ring):
    """``ring`` unchanged, from a computation that keeps the device busy a
    while first: a deposit that reads the result waits that long."""
    import jax.numpy as jnp

    x = jnp.full((512, 512), 1e-3, jnp.float32)
    x = jax.lax.fori_loop(0, 64, lambda _, x: jnp.tanh(x @ x), x)
    return ring + (x[0, 0] > 1e30).astype(ring.dtype)


def buffer_lifetime(lane: Lane) -> None:
    """Take the buffer that just went up for refilling, as a refill does,
    and overwrite all of it before the sweep.  The deposit is held back
    behind a busy device, so it runs after the overwrite unless the refill
    waits for it."""
    mb = lane.mb
    lane.round([1])                             # deposit and sweep compiled
    lane.put(0)
    mb._mb = _held(mb._mb)
    lane.ch.flush()
    mb._open_generation()                       # the other buffer
    mb._staged = None
    mb._open_generation()                       # the one that just went up
    junk = lane.oracle([np.full((1, T, T), 7.0, np.float32)] *
                       (1 if lane.kind == "singleton" else K))
    mb._staged[:] = junk
    lane.check(lane.sweep())                    # the published generation
    mb._staged[:] = 0
    mb._staged = None
    lane.round([2])


def counter(lane: Lane) -> None:
    """Twelve flushes allocate two buffers; every staged row matches the
    oracle on the words the kernels read."""
    ns = lane.mb.n_slots
    for r in range(12):
        for j in range(1 + r % 3):
            slot = (r + j) % ns
            xs = lane.put(slot, None if lane.kind == "singleton"
                          else 1 + (r + j) % K)
            np.testing.assert_array_equal(
                lane.read_words(lane.row(slot), len(xs)),
                lane.read_words(lane.oracle(xs), len(xs)))
        lane.ch.flush()
        lane.check(lane.sweep())
    assert lane.ch.stats["flushes"] == 12
    assert lane.ch.stats["gen_allocs"] == 2


def _held_bytes(v: np.ndarray) -> int:
    """Bytes of the outermost array ``v`` keeps alive."""
    while isinstance(v.base, np.ndarray):
        v = v.base
    return v.nbytes


def sparse_answers(lane: Lane) -> None:
    """One frame (a 1-sub container on the aggregate lane) in a sweep of a
    ring of many slots: its answer is a copy, not a view that keeps the
    whole readback alive for as long as the caller keeps the answer."""
    lane.put(0, 1)
    lane.ch.flush()
    got = lane.sweep()
    for vals in got.values():
        for v in vals:
            assert _held_bytes(v) <= 2 * v.nbytes, (_held_bytes(v), v.nbytes)
    lane.check(got)


SCENARIOS = {f.__name__: f for f in (exactly_once, stale_trailer,
                                      short_container, buffer_lifetime,
                                      counter, sparse_answers)}


def run_case(scenario: str, kind: str, shards: int) -> None:
    # a short container needs an aggregate lane: in its "singleton" case
    # the short frame is a singleton, a degenerate 1-sub container
    single = scenario == "short_container" and kind == "singleton"
    lane = Lane("agg" if single else kind, shards)
    lane.single = single
    SCENARIOS[scenario](lane)


def _run_two_shards() -> dict:
    """Every case on a 2-shard mesh, in this process (2 host devices)."""
    out = {}
    for scenario in SCENARIOS:
        for kind in KINDS:
            try:
                run_case(scenario, kind, 2)
                out[f"{scenario}/{kind}"] = "ok"
            except Exception:       # reported per case by the parent test
                out[f"{scenario}/{kind}"] = traceback.format_exc()
    return out


_TWO_SHARDS_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.environ["STAGING_TESTS_DIR"])
import test_device_staging as S
print("RESULTS " + json.dumps(S._run_two_shards()))
"""


@pytest.fixture(scope="module")
def two_shard_results():
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src",
               STAGING_TESTS_DIR=str(REPO / "tests"))
    r = subprocess.run([sys.executable, "-c", _TWO_SHARDS_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULTS ")]
    assert lines, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(lines[-1][len("RESULTS "):])


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_staging_reuse(scenario, kind, mesh, request):
    if mesh == "one_chip":
        run_case(scenario, kind, 1)
        return
    res = request.getfixturevalue("two_shard_results")[f"{scenario}/{kind}"]
    assert res == "ok", res


def test_pack_helpers_write_every_read_word_in_place():
    """``out=`` over a row full of another frame's words gives the same
    words the kernels read as a fresh pack: withheld trailer zeroed,
    descriptors past ``n`` zeroed."""
    rng = np.random.default_rng(5)
    bw = T * T
    sw = HDR_WORDS + bw + 1
    x = rng.standard_normal(bw).astype(np.float32)
    row = np.full(sw, 0xFFFFFFFF, np.uint32)
    pack_word_frame(x, sw, name_hash=9, no_trailer=True, out=row)
    np.testing.assert_array_equal(
        row, pack_word_frame(x, sw, name_hash=9, no_trailer=True))
    assert row[-1] == 0

    sw = HDR_WORDS + 2 * K + K * bw + 1
    xs = [rng.standard_normal(bw).astype(np.float32) for _ in range(2)]
    row = np.full(sw, TRAILER, np.uint32)
    pack_agg_word_frame(xs, [3, 4], K, bw, sw, no_trailer=True, out=row)
    fresh = pack_agg_word_frame(xs, [3, 4], K, bw, sw, no_trailer=True)
    head = HDR_WORDS + 2 * K + 2 * bw
    np.testing.assert_array_equal(row[:head], fresh[:head])
    assert row[-1] == 0 and not row[HDR_WORDS + 4:HDR_WORDS + 2 * K].any()
    # one [n, body_words] array packs as the list of its rows does
    np.testing.assert_array_equal(
        pack_agg_word_frame(np.stack(xs), [3, 4], K, bw, sw),
        pack_agg_word_frame(xs, [3, 4], K, bw, sw))
