"""Paper §4 microbenchmarks: ifunc vs UCX-AM latency (Fig. 3) and message
throughput (Fig. 4), plus the first-arrival link cost (§3.4 hash table).

Same protocol as the paper: the benchmark ifunc bumps a counter on the
target; the throughput bench fills a ring with frames, flushes, and waits
for the consumer; ping-pong halves a round trip.  Payload sizes sweep
1B..1MB.  Reported: us/msg and the ifunc-vs-AM ratio (the paper's
"latency reduction" / "throughput increase" curves).
"""

from __future__ import annotations

import os
import pathlib
import time

os.environ.setdefault("REPRO_IFUNC_LIB_DIR", str(pathlib.Path(__file__).resolve().parents[1] / "ifunc_libs"))

from repro.core import (AmContext, AmEndpoint, Context, RingBuffer, Status,
                        ifunc_msg_create, ifunc_msg_send_nbix, poll_ifunc,
                        poll_ring, register_ifunc)

SIZES = [1, 16, 256, 1 << 10, 2 << 10, 4 << 10, 8 << 10, 16 << 10, 64 << 10,
         256 << 10, 1 << 20]


def _pair(link_mode="remote"):
    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    src = Context("src", lib_dir=libdir)
    dst = Context("dst", lib_dir=libdir, link_mode=link_mode)
    ep = src.nic.connect(dst.nic)
    return src, dst, ep


def bench_ifunc_latency(n_iters: int = 300) -> list[dict]:
    """One-way latency (ping-pong/2) per payload size."""
    rows = []
    src, dst, ep = _pair()
    back = dst.nic.connect(src.nic)
    h_src = register_ifunc(src, "counter_bump")
    h_dst = register_ifunc(dst, "counter_bump")
    r_dst = dst.nic.mem_map(4 << 20)
    r_src = src.nic.mem_map(4 << 20)
    for size in SIZES:
        payload = b"x" * size
        targs_s, targs_d = {}, {}
        # warm the link caches (exclude first-arrival cost — measured separately)
        m = ifunc_msg_create(h_src, payload)
        ifunc_msg_send_nbix(ep, m, r_dst.base, r_dst.rkey)
        poll_ifunc(dst, r_dst.view(), None, targs_d)
        t0 = time.perf_counter()
        for _ in range(n_iters):
            m = ifunc_msg_create(h_src, payload)
            ifunc_msg_send_nbix(ep, m, r_dst.base, r_dst.rkey)
            while poll_ifunc(dst, r_dst.view(), None, targs_d) != Status.OK:
                pass
            m2 = ifunc_msg_create(h_dst, payload)
            ifunc_msg_send_nbix(back, m2, r_src.base, r_src.rkey)
            while poll_ifunc(src, r_src.view(), None, targs_s) != Status.OK:
                pass
        dt = (time.perf_counter() - t0) / n_iters / 2
        rows.append({"bench": "latency", "api": "ifunc", "size": size,
                     "us": dt * 1e6})
    return rows


def bench_am_latency(n_iters: int = 300) -> list[dict]:
    rows = []
    a, b = AmContext("a"), AmContext("b")
    a.register(1, lambda p, n, t: None)
    b.register(1, lambda p, n, t: None)
    ab, ba = AmEndpoint(a, b), AmEndpoint(b, a)
    for size in SIZES:
        payload = b"x" * size
        t0 = time.perf_counter()
        for _ in range(n_iters):
            ab.send(1, payload)
            while b.progress() == 0:
                pass
            ba.send(1, payload)
            while a.progress() == 0:
                pass
        dt = (time.perf_counter() - t0) / n_iters / 2
        rows.append({"bench": "latency", "api": "am", "size": size, "us": dt * 1e6})
    return rows


def bench_throughput(n_msgs: int = 1024) -> list[dict]:
    """Messages/s: fill the ring, flush, wait for consumer (paper §4.1).

    Rebuilt on the fig5 ``timeit`` discipline: per size, the ifunc and AM
    arms are timed as INTERLEAVED fill+drain chunks with GC parked, each
    reported as its best chunk (:func:`_best_us`).  The old
    one-shot-wall-clock shape was visibly noise-dominated — a single GC
    pause or scheduler preemption inside the one timed window produced
    non-monotone size curves (2.2k msgs/s at 4096B vs 18.2k at 8192B on
    the same host), and the ifunc-vs-AM ratio rode whichever arm caught
    the interference."""
    import gc

    CHUNK = 64
    rows = []
    src, dst, ep = _pair()
    h = register_ifunc(src, "counter_bump")
    for size in SIZES:
        payload = b"x" * size
        msg = ifunc_msg_create(h, payload)
        slot = 1 << max(msg.nbytes - 1, 1).bit_length()
        region = dst.nic.mem_map(slot * CHUNK)
        ring = RingBuffer(region, slot)
        targs = {}

        def _ifunc_chunk():
            t0 = time.perf_counter()
            sent = 0
            while sent < CHUNK:
                burst = min(ring.n_slots, CHUNK - sent)
                for _ in range(burst):   # source fills the buffer ...
                    m = ifunc_msg_create(h, payload)
                    ifunc_msg_send_nbix(ep, m, ring.slot_addr(ring.tail),
                                        region.rkey)
                    ring.tail += 1
                ep.flush()               # ... flushes ...
                done = 0
                while done < burst:      # ... and waits on the target
                    if poll_ring(dst, ring, targs) == Status.OK:
                        done += 1
                sent += burst
            return time.perf_counter() - t0

        a, b = AmContext("a", n_slots=256), AmContext("b", n_slots=256)
        b.register(1, lambda p, n, t: None)
        ab = AmEndpoint(a, b)

        def _am_chunk():
            t0 = time.perf_counter()
            sent = 0
            while sent < CHUNK:
                burst = min(128, CHUNK - sent)
                for _ in range(burst):   # AM: runtime buffers, just send
                    ab.send(1, payload)
                ab.flush()
                b.progress()
                sent += burst
            return time.perf_counter() - t0

        _ifunc_chunk(), _am_chunk()      # warm (link cache, slabs, JIT-free)
        chunks = {"ifunc": [], "am": []}
        gc.collect()
        gc.disable()
        try:
            for _ in range(max(n_msgs // CHUNK, 8)):
                chunks["ifunc"].append(_ifunc_chunk())
                chunks["am"].append(_am_chunk())
        finally:
            gc.enable()
        for api in ("ifunc", "am"):
            us = _best_us(chunks[api], CHUNK)
            rows.append({"bench": "throughput", "api": api, "size": size,
                         "msgs_per_s": 1e6 / us, "us": us})
    return rows


def bench_link_cost(n_names: int = 50) -> list[dict]:
    """First-arrival (link+verify) vs cached dispatch (§3.4 hash table)."""
    import shutil
    import tempfile

    srcdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    tmp = pathlib.Path(tempfile.mkdtemp())
    names = []
    base = (srcdir / "counter_bump.py").read_text()
    for i in range(n_names):
        nm = f"cb_{i:03d}"
        (tmp / f"{nm}.py").write_text(base.replace("counter_bump", nm))
        names.append(nm)
    src = Context("src", lib_dir=tmp)
    dst = Context("dst", lib_dir=tmp, link_mode="remote")
    ep = src.nic.connect(dst.nic)
    region = dst.nic.mem_map(1 << 20)
    targs = {}
    first, cached = [], []
    for nm in names:
        h = register_ifunc(src, nm)
        m = ifunc_msg_create(h, b"p")
        ifunc_msg_send_nbix(ep, m, region.base, region.rkey)
        t0 = time.perf_counter()
        assert poll_ifunc(dst, region.view(), None, targs) == Status.OK
        first.append(time.perf_counter() - t0)
        m = ifunc_msg_create(h, b"p")
        ifunc_msg_send_nbix(ep, m, region.base, region.rkey)
        t0 = time.perf_counter()
        assert poll_ifunc(dst, region.view(), None, targs) == Status.OK
        cached.append(time.perf_counter() - t0)
    shutil.rmtree(tmp)
    med = lambda xs: sorted(xs)[len(xs) // 2]
    return [
        {"bench": "link_cost", "api": "ifunc-first-arrival", "size": 1,
         "us": med(first) * 1e6},
        {"bench": "link_cost", "api": "ifunc-cached", "size": 1,
         "us": med(cached) * 1e6},
    ]


def bench_dispatcher_fanout(n_peers: int = 4, n_msgs: int = 256,
                            size: int = 1 << 10) -> list[dict]:
    """Transport-layer fan-out: one source dispatching to N peers through
    the Dispatcher (credits + batched flush + fair drain) vs the same
    message count hand-rolled over a single poll_ring loop.  Measures the
    multiplexing overhead of the unified layer."""
    from repro.core import Context, RingBuffer
    from repro.transport import Dispatcher, LoopbackFabric, ProgressEngine, RdmaFabric

    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    payload = b"x" * size
    slot = 1 << (size + 1500).bit_length()   # payload + frame overhead headroom
    rows = []

    d = Dispatcher(Context("src", lib_dir=libdir),
                   ProgressEngine(flush_threshold=16))
    for i in range(n_peers):
        fab = RdmaFabric() if i % 2 == 0 else LoopbackFabric()
        d.add_peer(f"p{i}", fab, Context(f"p{i}", lib_dir=libdir,
                                         link_mode="remote"),
                   n_slots=16, slot_size=slot)
    h = register_ifunc(d.src_ctx, "counter_bump")
    t0 = time.perf_counter()
    for _ in range(n_msgs):
        for name in d.peers:
            while not d.send(name, ifunc_msg_create(h, payload)):
                d.drain()
    d.drain()
    dt = time.perf_counter() - t0
    total = n_msgs * n_peers
    rows.append({"bench": "dispatch_fanout", "api": f"dispatcher-{n_peers}peer",
                 "size": size, "msgs_per_s": total / dt,
                 "us": dt / total * 1e6})

    # baseline: the old 1:1 poll_ring loop, same message count on one peer
    src, dst, ep = _pair()
    h1 = register_ifunc(src, "counter_bump")
    region = dst.nic.mem_map(slot * 16)
    ring = RingBuffer(region, slot)
    targs = {}
    t0 = time.perf_counter()
    for _ in range(total):
        m = ifunc_msg_create(h1, payload)
        ifunc_msg_send_nbix(ep, m, ring.slot_addr(ring.tail), region.rkey)
        ring.tail += 1
        while poll_ring(dst, ring, targs) != Status.OK:
            pass
    dt = time.perf_counter() - t0
    rows.append({"bench": "dispatch_fanout", "api": "poll_ring-1peer",
                 "size": size, "msgs_per_s": total / dt,
                 "us": dt / total * 1e6})
    return rows


def _best_us(chunk_times: list, chunk: int) -> float:
    """Best (minimum) per-call μs over chunked timings — the ``timeit``
    estimator.  The emulation shares a noisy host: GC pauses and scheduler
    preemptions can swing a mean (and even a median, under sustained
    interference) 2-3x between runs, while the fastest chunk is what the
    protocol actually costs.  Every fig5 cell uses this same estimator,
    so the cross-cell ratios CI asserts on (slim < full, slim_agg >= 2x
    slim) compare like with like."""
    return min(chunk_times) / chunk * 1e6


def bench_fig5_cached(n_iters: int = 200, sizes: list | None = None,
                      agg_k: int = 64) -> list[dict]:
    """Cached invocation (paper §3.4, 'Fig. 5'): per payload size, compare

    * ``full``     — every message re-injects the ~256 KiB bench_hot code
      section (first-arrival protocol repeated forever);
    * ``slim``     — code elided after the one warmup FULL frame; the
      target dispatches from its digest-keyed link cache (no sha256 on
      the path);
    * ``slim_agg`` — coalesced dispatch: ``agg_k`` cached invocations per
      FLAG_AGG container through the dispatcher's coalescing queue — one
      put, one ring slot, one sweep pass per K messages.  This is the
      cell that must close the per-message-overhead gap to AM;
    * ``am``       — the UCX-AM baseline (handler pre-registered, no code).

    Methodology: per size, the four cells' chunks are timed INTERLEAVED
    (full, slim, am, one aggregate batch, repeat), each cell reported as
    its best chunk (:func:`_best_us`), with GC parked for the duration —
    the ``timeit`` discipline.  Interleaving matters as much as the
    estimator: the cross-cell ratios CI asserts on (slim < full,
    slim_agg >= 2x slim) would otherwise ride CPU-frequency and
    host-contention drift between separately-timed phases.
    """
    import gc

    from repro.transport import Dispatcher, ProgressEngine, RdmaFabric

    CHUNK = 16
    sizes = sizes if sizes is not None else [16, 256, 4 << 10, 64 << 10]
    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    rows = []
    src, dst, ep = _pair()
    h = register_ifunc(src, "bench_hot")
    region = dst.nic.mem_map(4 << 20)
    targs = {}
    m = ifunc_msg_create(h, b"warm")          # warm the target's link cache
    ifunc_msg_send_nbix(ep, m, region.base, region.rkey)
    assert poll_ifunc(dst, region.view(), None, targs) == Status.OK
    for size in sizes:
        payload = b"x" * size

        def _singleton_chunk(slim):
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                msg = ifunc_msg_create(h, payload, slim=slim)
                ifunc_msg_send_nbix(ep, msg, region.base, region.rkey)
                while poll_ifunc(dst, region.view(), None,
                                 targs) != Status.OK:
                    pass
            return time.perf_counter() - t0

        a, b = AmContext("a"), AmContext("b")
        b.register(1, lambda p, n, t: None)
        ab = AmEndpoint(a, b)

        def _am_chunk():
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                ab.send(1, payload)
                while b.progress() == 0:
                    pass
            return time.perf_counter() - t0

        # coalescing is a small-message-rate lever: past the dispatcher's
        # max_sub_bytes policy cap (16 KiB) the wire is bandwidth-bound
        # and records BYPASS the queue as plain SLIM singletons.  The cell
        # still exists above the cap — there it measures bypass *parity*:
        # the dispatcher's coalescing machinery must not tax records the
        # policy declines to aggregate (check_bench holds it near the slim
        # singleton rate rather than to the 2x aggregation floor).
        do_agg = size <= 16 << 10
        nrec = agg_k if do_agg else 16
        src2 = Context("src_agg", lib_dir=libdir)
        dst2 = Context("dst_agg", lib_dir=libdir, link_mode="remote")
        d = Dispatcher(src2, ProgressEngine(flush_threshold=2 * agg_k))
        d.set_coalescing(True, max_subs=agg_k)
        # the slot must hold a FULL singleton fallback (~256 KiB of
        # code) AND as much of a K-record aggregate as possible; TWO
        # slots suffice (one container in flight at a time) and keep
        # the slab+region working set cache-resident between the
        # interleaved chunks.  The bypass arm instead sizes the ring for
        # its per-record singletons: one slot per in-flight record.
        if do_agg:
            slot = max(512 << 10, 1 << (size * agg_k + 4096).bit_length())
            d.add_peer("t", RdmaFabric(), dst2, n_slots=2, slot_size=slot,
                       target_args={})
        else:
            slot = max(512 << 10, 1 << (size + 4096).bit_length())
            d.add_peer("t", RdmaFabric(), dst2, n_slots=nrec,
                       slot_size=slot, target_args={})
        h2 = register_ifunc(src2, "bench_hot")
        assert d.send_ifunc("t", h2, b"warm")   # FULL: link + confirm
        d.drain()
        batch = [payload] * nrec

        def _agg_chunk():
            # the bulk enqueue: codec + queue state hoisted per batch —
            # this is the API a small-task storm actually uses.  Bypass
            # records are ring-paced: the poll both retires frames and
            # frees the credits the remainder of the batch needs.
            t0 = time.perf_counter()
            sent = d.send_ifunc_many("t", h2, batch)
            d.flush()
            d.poll()
            while sent < nrec:
                sent += d.send_ifunc_many("t", h2, batch[sent:])
                d.flush()
                d.poll()
            return time.perf_counter() - t0

        # warm every arm untimed (link caches, slabs, numpy paths)
        _singleton_chunk(False), _singleton_chunk(True), _am_chunk()
        _agg_chunk()
        d.drain()
        chunks = {"full": [], "slim": [], "am": [], "slim_agg": []}
        gc.collect()
        gc.disable()                             # timeit discipline: the
        try:                                     # collector's pauses are not
            for _ in range(max(n_iters // CHUNK, 8)):   # protocol cost
                chunks["full"].append(_singleton_chunk(False))
                chunks["slim"].append(_singleton_chunk(True))
                chunks["am"].append(_am_chunk())
                chunks["slim_agg"].append(_agg_chunk())
        finally:
            gc.enable()
        d.drain()
        peer = d.peers["t"]
        if do_agg:
            assert peer.stats["agg_subs"] >= len(chunks["slim_agg"]) * agg_k, \
                peer.stats
        else:
            # bypass-parity cell: every record must have shipped as a
            # singleton — zero containers proves the policy cap routed
            # around the queue instead of through it
            assert peer.stats.get("agg_sent", 0) == 0, peer.stats
        cells = [("full", CHUNK), ("slim", CHUNK), ("am", CHUNK),
                 ("slim_agg", nrec)]
        for cell, per in cells:
            us = _best_us(chunks[cell], per)
            rows.append({"bench": "fig5_cached", "api": cell, "size": size,
                         "cell": f"{cell}/{size}B", "us": us,
                         "msgs_per_s": 1e6 / us})
    return rows


def bench_graph_placement(n_iters: int = 60,
                          shard_edges: tuple = (1024, 8192, 65536)) -> list[dict]:
    """'fig_graph': the placement engine's three options, priced for real.

    Per shard size, one relax task (16-vertex frontier, constant degree 16)
    runs three ways:

    * ``migrate`` — graph_relax ships to the shard's owner (SLIM after the
      warmup FULL), only the frontier + updates cross the wire;
    * ``fetch``   — graph_fetch pulls the whole shard back as a reply,
      relax runs at the source (each iteration re-fetches: the cold case);
    * ``local``   — the shard was fetched once, relax reuses the replica.

    The shard is CSR-indexed (``tasks.graph``), so the relax *compute* is
    O(frontier degree) and identical everywhere, while a fetch moves
    O(edges) bytes — the migrate-vs-fetch gap must widen with shard size,
    which is exactly the cost-model assumption ``check_bench.py`` asserts
    on the largest size.
    """
    import numpy as np

    from repro.tasks import TaskRuntime
    from repro.tasks.graph import local_relax, pack_csr_shard
    from repro.transport import LoopbackFabric, ProgressEngine

    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    rng = np.random.default_rng(3)
    frontier = [(int(i), 0.5) for i in range(16)]
    DEG = 16

    rows = []
    for ne in shard_edges:
        nv = ne // DEG                  # constant out-degree: frontier work
        edges = [(u, int(rng.integers(0, 1 << 20)),
                  float(rng.uniform(0.1, 1)))
                 for u in range(nv) for _ in range(DEG)]
        packed = pack_csr_shard(0, nv, edges)
        src = Context("src", lib_dir=libdir)
        rt = TaskRuntime(src, engine=ProgressEngine(flush_threshold=8),
                         default_timeout=60.0)
        store = {"shards": {0: packed}}
        rt.add_peer("owner", LoopbackFabric(),
                    Context("owner", lib_dir=libdir, link_mode="remote"),
                    n_slots=8, slot_size=max(64 << 10, len(packed) + 4096),
                    target_args=store)
        h_relax = register_ifunc(src, "graph_relax")
        h_fetch = register_ifunc(src, "graph_fetch")
        nb = len(packed)
        # warm both verbs: link at the target, confirm digests (SLIM after)
        rt.submit("owner", h_relax, {"sid": 0, "frontier": frontier}).result()
        blob = rt.submit("owner", h_fetch, {"sid": 0}).result()
        t0 = time.perf_counter()
        for _ in range(n_iters):
            rt.submit("owner", h_relax,
                      {"sid": 0, "frontier": frontier}).result()
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "fig_graph", "api": "migrate", "size": nb,
                     "cell": f"migrate/{nb}B", "us": dt * 1e6,
                     "msgs_per_s": 1 / dt})
        t0 = time.perf_counter()
        for _ in range(n_iters):
            blob = rt.submit("owner", h_fetch, {"sid": 0}).result()
            local_relax(blob, frontier)
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "fig_graph", "api": "fetch", "size": nb,
                     "cell": f"fetch/{nb}B", "us": dt * 1e6,
                     "msgs_per_s": 1 / dt})
        t0 = time.perf_counter()
        for _ in range(n_iters):
            local_relax(blob, frontier)
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "fig_graph", "api": "local", "size": nb,
                     "cell": f"local/{nb}B", "us": dt * 1e6,
                     "msgs_per_s": 1 / dt})
    return rows


def bench_slab_pack(n_iters: int = 2000, code_len: int = 16 << 10,
                    payload_len: int = 4 << 10) -> list[dict]:
    """Send-path staging: the old pipeline (fresh bytearray per frame, then
    the ``bytes(data)`` wire copy the emulated NIC used to make) vs the new
    one (pack in place into a reused slab cell; the NIC copies straight out
    of the view — one copy total, zero allocations)."""
    from repro.core import frame as F

    code = b"c" * code_len
    digest = F.compute_digest(code)
    payload = b"p" * payload_len
    rows = []
    t0 = time.perf_counter()
    for _ in range(n_iters):
        frame = F.pack_frame("micro", code, payload, F.CodeKind.PYBC,
                             digest=digest)
        bytes(frame)                      # the legacy put_nbi staging copy
    dt = (time.perf_counter() - t0) / n_iters
    rows.append({"bench": "micro_slab", "api": "alloc", "size": code_len,
                 "cell": f"alloc+copy/{code_len + payload_len}B",
                 "us": dt * 1e6})
    slab = bytearray(F.HEADER_LEN + code_len + payload_len + F.TRAILER_LEN)
    t0 = time.perf_counter()
    for _ in range(n_iters):
        F.pack_frame_into(slab, "micro", code, payload, F.CodeKind.PYBC,
                          digest=digest)
    dt = (time.perf_counter() - t0) / n_iters
    rows.append({"bench": "micro_slab", "api": "slab", "size": code_len,
                 "cell": f"slab/{code_len + payload_len}B", "us": dt * 1e6})
    return rows


def bench_checksum(n_iters: int = 300, size: int = 64 << 10) -> list[dict]:
    """fletcher32: pure-Python byte loop vs the vectorized numpy closed
    form (sum + cumsum over 16-bit words)."""
    from repro.core import frame as F

    data = bytes(range(256)) * (size // 256)
    rows = []
    for cell, fn in (("pure", F.fletcher32_py), ("numpy", F.fletcher32)):
        t0 = time.perf_counter()
        for _ in range(n_iters if cell == "numpy" else max(n_iters // 20, 3)):
            fn(data)
        iters = n_iters if cell == "numpy" else max(n_iters // 20, 3)
        dt = (time.perf_counter() - t0) / iters
        rows.append({"bench": "micro_checksum", "api": cell, "size": size,
                     "cell": f"{cell}/{size}B", "us": dt * 1e6})
    return rows


def bench_header(n_iters: int = 4000, payload_len: int = 256) -> list[dict]:
    """micro_header: the per-frame header protocol cost — seal + peek +
    trailer check — as shipped (precompiled ``struct.Struct`` instances,
    one 48-word unpack for the header checksum) vs a naive reference that
    re-parses format strings and checksums the header byte-by-byte through
    a sliced memoryview (the pre-v2.3 code shape).  This cost is paid once
    per FRAME, which is exactly why aggregates amortize it K ways."""
    import struct as S

    from repro.core import frame as F

    code = b"c" * 64
    digest = F.compute_digest(code)
    payload = b"p" * payload_len
    buf = bytearray(F.HEADER_LEN + len(code) + payload_len + F.TRAILER_LEN)

    def naive_once():
        # the old send/poll shape: struct.pack with an inline format, a
        # fresh memoryview slice + per-byte fletcher, struct.unpack_from
        # with inline formats on every field access
        nb = "micro".encode().ljust(F.NAME_LEN, b"\0")
        payload_off = F.HEADER_LEN + len(code)
        frame_len = payload_off + payload_len + F.TRAILER_LEN
        buf[F.HEADER_LEN:payload_off] = code
        buf[payload_off:payload_off + payload_len] = payload
        hdr = S.pack(F._HEADER_FMT, F.MAGIC, frame_len, F.HEADER_LEN,
                     payload_off, int(F.CodeKind.PYBC), nb, 0, digest, 0,
                     payload_off + payload_len)
        buf[:F.SIGNAL_OFF] = hdr
        S.pack_into("<I", buf, F.SIGNAL_OFF, F.fletcher32_py(hdr))
        S.pack_into("<I", buf, frame_len - F.TRAILER_LEN, F.TRAILER)
        (magic,) = S.unpack_from("<I", buf, 0)
        (sig,) = S.unpack_from("<I", buf, F.SIGNAL_OFF)
        mv = memoryview(buf)[:F.SIGNAL_OFF]
        try:
            assert sig == F.fletcher32_py(mv)
        finally:
            mv.release()
        fields = S.unpack_from(F._HEADER_FMT, buf, 0)
        (t,) = S.unpack_from("<I", buf, frame_len - F.TRAILER_LEN)
        assert t == F.TRAILER
        return fields

    def fast_once():
        F.pack_frame_into(buf, "micro", code, payload, F.CodeKind.PYBC,
                          digest=digest)
        hdr = F.peek_header(buf)
        assert F.trailer_arrived(buf, hdr)
        return hdr

    rows = []
    for cell, fn in (("naive", naive_once), ("fast", fast_once)):
        fn()                                     # warm
        t0 = time.perf_counter()
        for _ in range(n_iters):
            fn()
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "micro_header", "api": cell,
                     "size": payload_len, "cell": f"{cell}/{payload_len}B",
                     "us": dt * 1e6})
    return rows


def bench_agg_parse(n_iters: int = 300, k: int = 64,
                    payload_len: int = 256) -> list[dict]:
    """micro_agg: decoding one K-record aggregate container — the
    per-record reference loop (``unpack_agg_py``: K ``struct.unpack_from``
    calls, K bounds checks, per-record signal-span bookkeeping, K
    ``AggSub`` allocations) vs the shipped vectorized parse
    (``parse_agg``: ONE numpy structured read over the sub-record table,
    ONE bounds check, ONE signal pass, columns instead of objects).
    ``parse_agg`` — not the ``unpack_agg`` compat projection, which
    re-materializes the K objects and gives the win back — is what the
    dispatcher's poll and reply paths actually call; this is the
    target-side per-container cost the fig5 ``slim_agg`` cell pays once
    per K messages."""
    from repro.core import frame as F

    digest = F.compute_digest(b"c" * 64)
    subs = [F.AggSub("micro", F.CodeKind.PYBC, digest, i + 1,
                     b"p" * payload_len) for i in range(k)]
    buf = bytearray(F.agg_payload_len(subs))
    n = F.pack_agg_into(memoryview(buf), subs)
    payload = memoryview(buf)[:n]
    assert len(F.unpack_agg_py(payload)) == k    # sanity
    assert F.parse_agg(payload).n == k
    rows = []
    for cell, fn in (("naive", F.unpack_agg_py), ("vectorized", F.parse_agg)):
        fn(payload)                              # warm
        t0 = time.perf_counter()
        for _ in range(n_iters):
            fn(payload)
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "micro_agg", "api": cell, "size": k,
                     "cell": f"{cell}/{k}sub", "us": dt * 1e6})
    return rows


def bench_device_agg(n_rounds: int = 3, agg_k: int = 64,
                     n_slots: int = 2) -> list[dict]:
    """'device_agg': the batched aggregate-container sweep vs the shipping
    per-slot singleton ring at the same K-sub-record workload (1-device
    mesh; Pallas mode follows the backend).

    * ``agg_sweep`` — all K sub-records arrive in ONE container slot; a
      single ring visit (one ``agg_ring_poll`` pass + ONE ``ifunc_vm``
      launch over all K bodies) retires the whole batch;
    * ``per_slot``  — the same K records as singleton word-frames through
      the n_slots-deep device ring: ceil(K / n_slots) ring visits, each
      paying the full per-visit fixed cost (poll-kernel dispatch,
      ``ifunc_vm`` launch, shard_map plumbing) to retire n_slots records.

    Both arms run the identical bound μVM program over identical 128x128
    f32 tiles, so the compute cancels; what the ratio prices is the fixed
    per-visit cost amortized K ways vs n_slots ways — the device mirror
    of host coalescing.  Reported per sub-record; ``check_bench.py``
    holds ``agg_sweep`` to >= 2x the ``per_slot`` message rate."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codegen import assemble
    from repro.core.device_mailbox import (pack_agg_word_frame,
                                           pack_word_frame, make_agg_sweep,
                                           make_sweep)
    from repro.kernels.ring_poll import HDR_WORDS
    from repro.parallel.sharding import make_mesh

    T, n_tiles = 128, 1
    body_words = n_tiles * T * T
    mesh = make_mesh((1,), ("mb",), devices=np.array(jax.devices()[:1]))
    prog = assemble([
        ("loadp", 0), ("loade", 1, 0), ("matmul", 2, 0, 1),
        ("relu", 2, 2), ("store", 0, 2),
    ], symbols=("W",))
    ext = jnp.asarray(np.eye(T, dtype="float32"))[None, None]
    rng = np.random.default_rng(7)
    pays = [rng.standard_normal((T, T)).astype("float32")
            for _ in range(agg_k)]
    bound = 0x1234ABCD

    slot_words_a = HDR_WORDS + 2 * agg_k + agg_k * body_words + 1
    mb_a = np.zeros((1, 1, slot_words_a), np.uint32)
    mb_a[0, 0] = pack_agg_word_frame(pays, [bound] * agg_k, agg_k,
                                     body_words, slot_words_a)
    mb_a = jnp.asarray(mb_a)
    sweep_a = make_agg_sweep(mesh, "mb", prog, agg_k, n_tiles, T,
                             bound_hash=bound)

    slot_words_s = HDR_WORDS + body_words + 1
    mb_s = np.zeros((1, n_slots, slot_words_s), np.uint32)
    for j in range(n_slots):
        mb_s[0, j] = pack_word_frame(pays[j], slot_words_s)
    mb_s = jnp.asarray(mb_s)
    sweep_s = make_sweep(mesh, "mb", prog, n_tiles, T)

    jax.block_until_ready(sweep_a(mb_a, ext))    # compile + warm both arms
    jax.block_until_ready(sweep_s(mb_s, ext))
    visits = -(-agg_k // n_slots)

    def _agg_round():
        t0 = time.perf_counter()
        jax.block_until_ready(sweep_a(mb_a, ext))
        return time.perf_counter() - t0

    def _slot_round():
        t0 = time.perf_counter()
        for _ in range(visits):
            jax.block_until_ready(sweep_s(mb_s, ext))
        return time.perf_counter() - t0

    chunks = {"agg_sweep": [], "per_slot": []}
    gc.collect()
    gc.disable()
    try:
        for _ in range(n_rounds):                # interleaved, min-of-rounds
            chunks["agg_sweep"].append(_agg_round())
            chunks["per_slot"].append(_slot_round())
    finally:
        gc.enable()
    rows = []
    for cell in ("agg_sweep", "per_slot"):
        us = _best_us(chunks[cell], agg_k)
        rows.append({"bench": "device_agg", "api": cell, "size": agg_k,
                     "cell": f"{cell}/K{agg_k}", "us": us,
                     "msgs_per_s": 1e6 / us})
    return rows


def bench_uvm(n_tiles: int = 8, iters: int = 5) -> list[dict]:
    """Device-tier μVM execution cost per injected program."""
    import numpy as np

    from repro.core.codegen import assemble
    from repro.kernels import ops as K

    prog = assemble([
        ("loadp", 0), ("loade", 1, 0), ("matmul", 2, 0, 1),
        ("relu", 2, 2), ("store", 0, 2),
    ], symbols=("W",))
    pay = np.random.default_rng(0).standard_normal((n_tiles, 128, 128)).astype("float32")
    W = np.eye(128, dtype="float32")
    K.uvm_execute(prog, pay, [W])  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        K.uvm_execute(prog, pay, [W])
    dt = (time.perf_counter() - t0) / iters
    return [{"bench": "uvm", "api": "ifunc-vm", "size": n_tiles * 128 * 128 * 4,
             "us": dt * 1e6}]


def bench_flow_chain(n_iters: int = 40, stage_counts: tuple = (3, 5),
                     payload_bytes: int = 32 << 10) -> list[dict]:
    """'fig_flow': an N-stage continuation chain vs the same N stages as
    host-coordinated round-trips.

    Both arms run the identical ``flow_xform`` stage at the identical
    peers over the identical fabrics (alternating RDMA / loopback), so
    the compute and the per-hop wire work cancel out.  What differs is
    the *coordination*: the chain submits one frame and the result
    forwards peer-to-peer via continuation descriptors (N+1 frames, no
    intermediate reply codec passes, one future); the round-trip arm
    pays, per stage, a reply encode + reply frame + drain + decode + a
    fresh submit (2N frames, N futures).  An N-stage chain finishing
    faster than N round-trips is the PR's acceptance bar, enforced by
    ``check_bench.py`` on the persisted rows.
    """
    from repro.flow import Flow, FlowEngine
    from repro.tasks import TaskRuntime
    from repro.transport import LoopbackFabric, ProgressEngine, RdmaFabric

    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    blob = bytes(range(256)) * (payload_bytes // 256)
    SLOT = 128 << 10
    rows = []
    for n_stages in stage_counts:
        peers = [f"hop{i}" for i in range(n_stages)]
        fabrics = [RdmaFabric() if i % 2 == 0 else LoopbackFabric()
                   for i in range(n_stages)]
        expect = blob if n_stages % 2 == 0 else blob[::-1]

        # -- continuation chain ------------------------------------------
        eng = FlowEngine(Context("host", lib_dir=libdir),
                         default_timeout=60.0)
        for p, fab in zip(peers, fabrics):
            eng.add_node(p, fab, slot_size=SLOT)
        flow = Flow(f"chain{n_stages}")
        for p in peers:
            flow.stage("flow_xform", at=p)
        assert eng.submit(flow, blob).result() == expect  # link + warm SLIM
        t0 = time.perf_counter()
        for _ in range(n_iters):
            assert eng.submit(flow, blob).result() == expect
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "fig_flow", "api": "chain",
                     "size": payload_bytes,
                     "cell": f"chain/{n_stages}stage", "us": dt * 1e6,
                     "msgs_per_s": 1 / dt})

        # -- host-coordinated round-trips --------------------------------
        rt = TaskRuntime(Context("host-rt", lib_dir=libdir),
                         engine=ProgressEngine(flush_threshold=8,
                                               inflight_window="trailer"),
                         default_timeout=60.0)
        for p, fab in zip(peers, fabrics):
            rt.add_peer(p, fab, Context(p, lib_dir=libdir),
                        n_slots=8, slot_size=SLOT, target_args={})
        h = register_ifunc(rt.ctx, "flow_xform")

        def roundtrip(data):
            for p in peers:
                data = rt.submit(p, h, data).result()
            return data

        assert roundtrip(blob) == expect                  # link + warm SLIM
        t0 = time.perf_counter()
        for _ in range(n_iters):
            assert roundtrip(blob) == expect
        dt = (time.perf_counter() - t0) / n_iters
        rows.append({"bench": "fig_flow", "api": "roundtrip",
                     "size": payload_bytes,
                     "cell": f"roundtrip/{n_stages}stage", "us": dt * 1e6,
                     "msgs_per_s": 1 / dt})
    return rows


def bench_stream(n_iters: int = 64,
                 sizes: list | None = None) -> list[dict]:
    """'fig_stream': streamed large payloads vs store-and-forward vs AM,
    64 KiB -> 16 MiB — the 64 KiB-cliff killer's acceptance sweep.

    Four cells per payload size, interleaved chunks, min-of-chunks, GC
    parked (the fig5 timeit discipline).  Every cell is measured at the
    BARE API level — endpoint puts + direct ``poll_ifunc`` — exactly like
    fig5's slim/full cells, so the ratios price the wire protocol, not
    any dispatcher bookkeeping:

    * ``stream``  — frame v2.5 FLAG_STREAM, warm SLIM: ONE scatter-gather
      put gathers a pre-sealed header|descriptor|chunk-glue template and
      the payload chunks as zero-copy views (the frame trailer withheld
      until flush — the delivery barrier), and the streaming-aware
      ``stream_sink`` executes each chunk on arrival;
    * ``sf``      — store-and-forward SLIM singleton: the whole payload
      copied into one frame, one put, target waits for the full trailer
      (what the coalescing bypass shipped before this PR);
    * ``sf_full`` — store-and-forward with the code section re-injected
      every message;
    * ``am``      — the UCX-AM baseline (handler pre-registered).

    The store-and-forward arms pay a frame *build* (payload copied into
    the frame bytes) plus the put; the stream arm's put gathers straight
    from the caller's payload — one payload traversal instead of two,
    which is exactly the bandwidth lever the sweep exists to show.
    check_bench holds ``stream`` to <= sf_full and <= am at every size,
    <= sf at every size past 256 KiB, and >= 1.5x the frozen PR6 slim
    rate at 64 KiB.
    """
    import gc

    from repro.core import frame as F
    from repro.transport import RdmaFabric

    sizes = sizes if sizes is not None else [
        64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20]
    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    rows = []
    for size in sizes:
        payload = b"x" * size
        CHUNK = max(2, min(16, (2 << 20) // size))

        # the 16 MiB cells build frames past the default policy bound
        # (1<<24); the bench prices transport, not the bound, so both
        # receiving contexts get a policy sized to the sweep
        from repro.core.security import SecurityPolicy
        pol = SecurityPolicy(max_frame_len=1 << 26)

        # -- stream arm: bare api, one gathered put from a template ------
        src = Context("src_stream", lib_dir=libdir)
        dst2 = Context("dst_stream", lib_dir=libdir, link_mode="remote",
                       policy=pol)
        h = register_ifunc(src, "stream_sink")
        lib = h.lib
        chunk = min(size, 256 << 10)
        n_chunks = -(-size // chunk)
        cell = chunk + F.CHUNK_OVERHEAD
        plen = F.stream_payload_len(n_chunks, cell)
        slot_size = 1 << (F.HEADER_LEN + len(lib.code) + plen
                          + F.TRAILER_LEN).bit_length()
        fab = RdmaFabric()
        mb = fab.open_mailbox(dst2, 2, slot_size)
        sep = fab.connect(src, mb).ep
        raddr, rkey = mb.slot_addr(0), mb.region.rkey
        key0, view0 = mb.slot_coords(0), mb.slot_view(0)
        targs_stream: dict = {}
        pv = memoryview(payload)

        def _build(slim):
            # pre-sealed frame template: header + descriptor + chunk glue
            # (headers/seals) staged once in a local slab; per message the
            # payload rides as zero-copy views between the glue runs.  The
            # last seal abuts the frame trailer seal_frame already wrote,
            # so the tail is one merged (withheld) segment.
            sflags = F.SFLAG_EXEC_ON_ARRIVAL if lib.streaming else 0
            desc = F.StreamDesc(size, n_chunks, chunk, n_chunks, 0,
                                sflags, cell, 1)
            code = b"" if slim else lib.code
            slab = bytearray(slot_size)
            flen = F.seal_frame(slab, lib.name, code, lib.kind, plen,
                                digest=lib.code_digest, slim=slim,
                                flags=F.FLAG_STREAM)
            F.pack_stream_desc(slab, F.HEADER_LEN + len(code), desc)
            prefix = F.HEADER_LEN + len(code) + F.STREAM_DESC_LEN
            segs, run_s = [], 0
            for seq in range(n_chunks):
                coff = prefix + desc.cell_off(seq)
                data = pv[seq * chunk:(seq + 1) * chunk]
                run_e = coff + F.CHUNK_HDR_LEN
                F.pack_chunk_into(slab, coff, run_e + len(data), seq,
                                  len(data), len(data), 0, nonce=desc.nonce)
                segs.append((run_s, memoryview(slab)[run_s:run_e]))
                segs.append((run_e, data))
                run_s = run_e + len(data)
            segs.append((run_s, memoryview(slab)[run_s:flen]))
            return slab, segs

        full_slab, full_segs = _build(False)      # FULL: link + confirm
        sep.putv_nbi(full_segs, raddr, rkey, withhold_tail=F.TRAILER_LEN)
        sep.flush()
        assert poll_ifunc(dst2, view0, None, targs_stream,
                          streams=mb.streams, stream_key=key0) == Status.OK
        assert targs_stream["result"] == size
        slab, segs = _build(True)                 # warm SLIM template
        # prepared WR: validation + offset resolution amortized once; the
        # per-post cost is what hardware charges — rkey re-check + gather
        wr = sep.prepare_putv(segs, raddr, rkey,
                              withhold_tail=F.TRAILER_LEN)

        def _stream_chunk():
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                wr.post()
                sep.flush()
                while poll_ifunc(dst2, view0, None, targs_stream,
                                 streams=mb.streams,
                                 stream_key=key0) != Status.OK:
                    pass
            return time.perf_counter() - t0

        # -- store-and-forward arms: raw api singletons ------------------
        s2, dst, ep = _pair()
        dst.policy = pol
        h2 = register_ifunc(s2, "stream_sink")
        region = dst.nic.mem_map(1 << (size + 8192).bit_length())
        targs_sf: dict = {}
        m = ifunc_msg_create(h2, payload)         # warm the link cache
        ifunc_msg_send_nbix(ep, m, region.base, region.rkey)
        assert poll_ifunc(dst, region.view(), None, targs_sf) == Status.OK
        assert targs_sf["result"] == size

        def _sf_chunk(slim):
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                msg = ifunc_msg_create(h2, payload, slim=slim)
                ifunc_msg_send_nbix(ep, msg, region.base, region.rkey)
                while poll_ifunc(dst, region.view(), None,
                                 targs_sf) != Status.OK:
                    pass
            return time.perf_counter() - t0

        # -- AM baseline -------------------------------------------------
        a, b = AmContext("a"), AmContext("b")
        got = []
        b.register(1, lambda p, n, t: got.append(n))
        ab = AmEndpoint(a, b)

        def _am_chunk():
            t0 = time.perf_counter()
            for _ in range(CHUNK):
                ab.send(1, payload)
                while b.progress() == 0:
                    pass
            return time.perf_counter() - t0

        _stream_chunk(), _sf_chunk(True), _sf_chunk(False), _am_chunk()
        chunks = {"stream": [], "sf": [], "sf_full": [], "am": []}
        gc.collect()
        gc.disable()
        try:
            for _ in range(max(n_iters // CHUNK, 8)):
                chunks["stream"].append(_stream_chunk())
                chunks["sf"].append(_sf_chunk(True))
                chunks["sf_full"].append(_sf_chunk(False))
                chunks["am"].append(_am_chunk())
        finally:
            gc.enable()
        assert dst2.stats["rejected"] == 0 and dst2.stats["nacks"] == 0, \
            dst2.stats
        # FULL warm + (warmup round + timed rounds) x CHUNK messages,
        # every one a completed stream
        assert dst2.stats.get("streams", 0) == \
            1 + CHUNK * (1 + len(chunks["stream"])), dst2.stats
        assert targs_stream["result"] == size and targs_sf["result"] == size
        assert got and got[-1] == size
        for cell in ("stream", "sf", "sf_full", "am"):
            us = _best_us(chunks[cell], CHUNK)
            rows.append({"bench": "fig_stream", "api": cell, "size": size,
                         "cell": f"{cell}/{size}B", "us": us,
                         "msgs_per_s": 1e6 / us})
    return rows

def bench_obs_overhead(agg_iters: int = 4096, agg_k: int = 64,
                       stream_iters: int = 192,
                       stream_size: int = 1 << 20) -> list[dict]:
    """'obs_overhead': the telemetry layer's hot-path tax, measured the
    only way a <=5% claim survives a shared CI host — as a SAME-RUN
    ratio between two identically-built dispatchers whose chunks are
    timed INTERLEAVED (the fig5 timeit discipline: min-of-chunks, GC
    parked):

    * ``agg_on`` / ``agg_off``       — the fig5 ``slim_agg`` shape
      (``agg_k`` x 256 B cached records per FLAG_AGG container), with
      the default counters-only ``Obs()`` vs ``Obs(enabled=False)``;
    * ``stream_on`` / ``stream_off`` — dispatcher-level FLAG_STREAM
      sends (1 MiB in 64 KiB chunks), same two arms.

    The ``*_on`` rows persist ``ratio = off_us / on_us`` (1.0 = free,
    0.95 = 5% tax); check_bench holds every ratio >= 0.95 from PR8 on.
    The defaults give the min estimator >= 48 chunks per arm — with the
    original ~10, a single noisy-vs-clean min pairing swung the ratio
    past the gate a third of the time on a loaded host (PR 9 fix).
    Tracing is NOT measured here: counters-only is the always-on default
    the benchmarks and production paths run under; span tracing is the
    opt-in debug mode and buys its cost knowingly.
    """
    import gc

    from repro.obs import Obs
    from repro.transport import Dispatcher, ProgressEngine, RdmaFabric

    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    rows = []

    # -- aggregate arms: the fig5 slim_agg shape -------------------------
    size = 256
    payload = b"x" * size
    slot = max(512 << 10, 1 << (size * agg_k + 4096).bit_length())

    def _mk_agg(tag, obs):
        src = Context(f"src_{tag}", lib_dir=libdir)
        dst = Context(f"dst_{tag}", lib_dir=libdir, link_mode="remote")
        d = Dispatcher(src, ProgressEngine(flush_threshold=2 * agg_k),
                       obs=obs)
        d.set_coalescing(True, max_subs=agg_k)
        d.add_peer("t", RdmaFabric(), dst, n_slots=2, slot_size=slot,
                   target_args={})
        h = register_ifunc(src, "bench_hot")
        assert d.send_ifunc("t", h, b"warm")   # FULL: link + confirm
        d.drain()
        return d, h

    d_on, h_on = _mk_agg("obs_on", Obs("bench_on"))
    d_off, h_off = _mk_agg("obs_off", Obs("bench_off", enabled=False))
    batch = [payload] * agg_k

    def _agg_chunk(d, h):
        t0 = time.perf_counter()
        sent = d.send_ifunc_many("t", h, batch)
        d.flush()
        d.poll()
        while sent < agg_k:
            sent += d.send_ifunc_many("t", h, batch[sent:])
            d.flush()
            d.poll()
        return time.perf_counter() - t0

    _agg_chunk(d_on, h_on), _agg_chunk(d_off, h_off)   # warm both arms
    chunks = {"agg_on": [], "agg_off": []}
    gc.collect()
    gc.disable()
    try:
        for _ in range(max(agg_iters // agg_k, 10)):
            chunks["agg_on"].append(_agg_chunk(d_on, h_on))
            chunks["agg_off"].append(_agg_chunk(d_off, h_off))
    finally:
        gc.enable()
    d_on.drain(), d_off.drain()
    # the on arm must actually have observed (else the ratio is a lie)
    assert d_on.obs.rtt_hist.count > 0 and len(d_on.obs.recorder) > 0
    assert d_off.obs.rtt_hist.count == 0 and len(d_off.obs.recorder) == 0

    # -- stream arms: dispatcher-level FLAG_STREAM -----------------------
    SCH = 4                            # streams per timed chunk

    def _mk_stream(tag, obs):
        src = Context(f"src_{tag}", lib_dir=libdir)
        dst = Context(f"dst_{tag}", lib_dir=libdir, link_mode="remote")
        d = Dispatcher(src, ProgressEngine(flush_threshold=8), obs=obs)
        d.add_peer("t", RdmaFabric(), dst, n_slots=2, slot_size=512 << 10,
                   target_args={})
        h = register_ifunc(src, "stream_sink")
        return d, h

    s_on, sh_on = _mk_stream("st_on", Obs("st_on"))
    s_off, sh_off = _mk_stream("st_off", Obs("st_off", enabled=False))
    blob = b"s" * stream_size

    def _stream_chunk(d, h):
        t0 = time.perf_counter()
        for _ in range(SCH):
            while not d.send_stream("t", h, blob, chunk_bytes=64 << 10,
                                    window=8):
                d.drain()
            d.drain()
        return time.perf_counter() - t0

    _stream_chunk(s_on, sh_on), _stream_chunk(s_off, sh_off)
    chunks["stream_on"], chunks["stream_off"] = [], []
    gc.collect()
    gc.disable()
    try:
        for _ in range(max(stream_iters // SCH, 8)):
            chunks["stream_on"].append(_stream_chunk(s_on, sh_on))
            chunks["stream_off"].append(_stream_chunk(s_off, sh_off))
    finally:
        gc.enable()
    assert s_on.peers["t"].stats["streams"] > 0
    assert s_on.obs.rtt_hist.count > 0 and s_off.obs.rtt_hist.count == 0

    for arm, per, sz in (("agg", agg_k, size), ("stream", SCH, stream_size)):
        us_off = _best_us(chunks[f"{arm}_off"], per)
        us_on = _best_us(chunks[f"{arm}_on"], per)
        rows.append({"bench": "obs_overhead", "api": f"{arm}_off",
                     "size": sz, "cell": f"{arm}_off/{sz}B", "us": us_off,
                     "msgs_per_s": 1e6 / us_off})
        rows.append({"bench": "obs_overhead", "api": f"{arm}_on",
                     "size": sz, "cell": f"{arm}_on/{sz}B", "us": us_on,
                     "msgs_per_s": 1e6 / us_on, "ratio": us_off / us_on})
    return rows


def bench_serve(fleet_sizes: tuple = (1, 2), host_slots: int = 8,
                decode_slots: int = 16, plen: int = 8, max_new: int = 16,
                repeats: int = 3) -> list[dict]:
    """'fig_serve': open-loop serving throughput — the disaggregated
    prefill/decode fabric vs the single-host server (PR 9).

    A synthetic client fleet enqueues N requests up front (open loop,
    N = 4x the decode tier's aggregate slots — hundreds of concurrent
    sequences at the largest fleet) and each arm serves the entire
    fleet; tok/s counts every emitted token, req/s counts completions.

    The arms embody the deployment asymmetry under test: the single-host
    ``Server`` runs prefill and decode on one engine with ``host_slots``
    batch slots (admission prefills serialize with decode on the same
    engine); a disaggregated fleet of F prefill + F decode peers batches
    same-length prompts into single prefill forwards, streams each KV
    cache to a decode peer as a FLAG_STREAM payload, and runs decode-ONLY
    peers at ``decode_slots`` (2x host) batch depth — the memory and
    interference headroom that motivates prefill/decode disaggregation.
    Both arms run the same jitted steps (shared via
    ``train.serve.jit_*_step``), so the delta is deployment shape, not
    compilation luck.

    Rows: ``host/cN`` and ``disagg/cN`` carry us/token (+ tok/s in
    ``msgs_per_s``); disagg rows carry ``ratio`` = host us/token over
    disagg us/token (>= 1 means the fabric sustains the baseline);
    ``disagg_req/cN`` carries req/s.  check_bench (PR >= 9) holds the
    largest-fleet ratio >= 1 and its req/s over a floor.
    """
    import gc

    import jax
    import numpy as np

    from repro.models import transformer as T
    from repro.serving import TINY, Request, Server, ServingFabric

    params = T.init_params(TINY, jax.random.PRNGKey(0))
    cache_len = 64
    assert plen + max_new <= cache_len

    def mk_reqs(n):
        rng = np.random.default_rng(17)
        return [Request(i, rng.integers(0, TINY.vocab_size, size=plen,
                                        dtype=np.int32), max_new=max_new)
                for i in range(n)]

    def run_host(n):
        srv = Server(TINY, params, host_slots, cache_len)
        rs = mk_reqs(n)
        pend = list(rs)
        t0 = time.perf_counter()
        while pend or srv.active:
            while pend and srv.admit(pend[0]):
                pend.pop(0)
            srv.tick()
        dt = time.perf_counter() - t0
        return sum(len(r.out) for r in rs), dt

    def run_disagg(n, fleet):
        fab = ServingFabric(TINY, params, n_prefill=fleet, n_decode=fleet,
                            batch_slots=decode_slots, cache_len=cache_len)
        rs = mk_reqs(n)
        t0 = time.perf_counter()
        done = fab.run(rs)
        dt = time.perf_counter() - t0
        assert len(done) == n and fab.buffered_installs() == 0
        return sum(len(r.out) for r in done.values()), dt

    sizes = {f: 4 * decode_slots * f for f in fleet_sizes}
    # warm every shape both arms will hit (jit caches are shared)
    run_host(2 * host_slots)
    for f in fleet_sizes:
        run_disagg(2 * decode_slots * f, f)

    rows = []
    gc.collect()
    gc.disable()
    try:
        for f in fleet_sizes:
            n = sizes[f]
            h_us, d_us, d_dt = [], [], []
            for _ in range(repeats):
                toks, dt = run_host(n)
                h_us.append(dt / toks * 1e6)
                toks, dt = run_disagg(n, f)
                d_us.append(dt / toks * 1e6)
                d_dt.append(dt)
            host_us, disagg_us = min(h_us), min(d_us)
            req_s = n / min(d_dt)
            rows.append({"bench": "fig_serve", "api": "host", "size": n,
                         "cell": f"host/c{n}", "us": host_us,
                         "msgs_per_s": 1e6 / host_us})
            rows.append({"bench": "fig_serve", "api": "disagg", "size": n,
                         "cell": f"disagg/c{n}", "us": disagg_us,
                         "msgs_per_s": 1e6 / disagg_us,
                         "ratio": host_us / disagg_us})
            rows.append({"bench": "fig_serve", "api": "disagg_req",
                         "size": n, "cell": f"disagg_req/c{n}",
                         "us": 1e6 / req_s, "msgs_per_s": req_s})
    finally:
        gc.enable()
    return rows


def bench_elastic(deadlines_ms: tuple = (20, 50, 100), repeats: int = 3,
                  n_msgs: int = 1024) -> list[dict]:
    """'fig_elastic': elastic-recovery latency vs heartbeat deadline plus
    the control plane's price against the data plane (PR 10).

    Recovery arm: a two-peer fleet heartbeats under an
    ``ElasticController`` riding the dispatcher poll loop; the
    ``FaultInjector`` kills one peer with a task in flight and the timed
    window runs kill -> recovery complete (peer retired from the
    dispatcher, in-flight future failed with TransportError, generation
    bumped).  Rows ``recover/<D>ms`` carry us = time-to-recover (best of
    ``repeats``) and ``ratio`` = recovery time over the deadline — the
    whole point of a heartbeat deadline is that detection is bounded by
    it, so check_bench (PR >= 10) holds ratio in [0.8, 3.0]: recovery
    tracks the configured deadline, not poll-loop luck.

    Overhead arm: ``hb_overhead`` prices the control ring against the
    slim data path.  ``n_msgs`` warm tasks stream through the same fleet
    under a 0.5s deadline (2 members x 3 beats/deadline = 12 beats/s of
    nominal control traffic) and ratio = nominal beats-per-second over
    measured task msgs-per-second.  check_bench holds ratio <= 0.02 —
    the <=2% heartbeat budget from ROADMAP item 4.
    """
    import gc

    from repro.core import register_ifunc
    from repro.runtime import ElasticController, FleetState
    from repro.tasks import TaskRuntime
    from repro.transport import (FaultInjector, LoopbackFabric,
                                 ProgressEngine, RdmaFabric, TransportError)

    libdir = pathlib.Path(os.environ["REPRO_IFUNC_LIB_DIR"])
    names = ("pa", "pb")

    def mk(deadline_s):
        src = Context("src", lib_dir=libdir)
        rt = TaskRuntime(src, engine=ProgressEngine(flush_threshold=64,
                                                    inflight_window="trailer"),
                         default_timeout=30.0)
        fabs, ctxs = {}, {}
        for i, name in enumerate(names):
            fabs[name] = RdmaFabric() if i % 2 == 0 else LoopbackFabric()
            ctxs[name] = Context(name, lib_dir=libdir, link_mode="remote")
            rt.add_peer(name, fabs[name], ctxs[name], n_slots=8,
                        slot_size=16 << 10, target_args={})
        fleet = FleetState(list(names), heartbeat_deadline=deadline_s)
        inj = FaultInjector()
        ec = ElasticController(rt, fleet, injector=inj)  # auto_poll rides
        for name in names:                               # rt.progress()
            ec.watch(name, fabs[name], ctxs[name])
        h = register_ifunc(src, "task_sum")
        return rt, ec, inj, h

    def settle(rt, fut):
        rt.flush()
        while not fut.done():
            rt.progress()

    def run_recover(deadline_s):
        rt, ec, inj, h = mk(deadline_s)
        f = rt.submit("pa", h, b"\x01" * 8)   # warm rings + fold a beat
        settle(rt, f)
        f.result()
        rt.progress()                          # freshest possible last_seen
        inj.kill_peer("pa")
        doomed = rt.submit("pa", h, b"\x02" * 8)
        rt.flush()
        t0 = time.perf_counter()
        while "pa" in rt.dispatcher.peers:     # poll loop drives detection
            rt.progress()
        dt = time.perf_counter() - t0
        assert doomed.done(), "fail_inflight should resolve the future"
        try:
            doomed.result()
            raise AssertionError("future on the dead peer must fail")
        except TransportError:
            pass
        assert ec.stats["deaths"] == 1 and rt.generation > 0
        return dt

    def run_overhead(deadline_s=0.5):
        rt, ec, _inj, h = mk(deadline_s)
        payload = b"\x05" * 64
        for name in names:                     # warm the SLIM cache
            settle(rt, rt.submit(name, h, payload))
        t0 = time.perf_counter()
        i = 0
        while i < n_msgs:
            burst = [rt.submit(names[i % len(names)], h, payload)
                     for _ in range(min(8, n_msgs - i))]
            i += len(burst)
            rt.flush()
            while not all(f.done() for f in burst):
                rt.progress()
        dt = time.perf_counter() - t0
        msgs_per_s = n_msgs / dt
        beats_per_s = len(names) * 3.0 / deadline_s   # interval=deadline/3
        return msgs_per_s, beats_per_s / msgs_per_s

    rows = []
    run_recover(deadlines_ms[0] / 1e3)         # warm (link cache, slabs)
    gc.collect()
    gc.disable()
    try:
        for dms in deadlines_ms:
            dt = min(run_recover(dms / 1e3) for _ in range(repeats))
            rows.append({"bench": "fig_elastic", "api": "recover",
                         "size": dms, "cell": f"recover/{dms}ms",
                         "us": dt * 1e6, "ratio": dt / (dms / 1e3)})
        msgs_per_s, ratio = run_overhead()
        rows.append({"bench": "fig_elastic", "api": "hb", "size": n_msgs,
                     "cell": "hb_overhead", "us": 1e6 / msgs_per_s,
                     "msgs_per_s": msgs_per_s, "ratio": ratio})
    finally:
        gc.enable()
    return rows
