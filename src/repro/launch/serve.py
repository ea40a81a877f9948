"""Serving driver — a thin CLI over :mod:`repro.serving`.

Two deployment shapes, one decode engine:

* ``--mode host`` (default): single-host :class:`~repro.serving.Server`
  fed ``srv_enqueue`` frames by an :class:`~repro.serving.IfuncFrontend`
  over a credit-flow-controlled ring.
* ``--mode disagg``: the disaggregated
  :class:`~repro.serving.ServingFabric` — dedicated prefill peers stream
  each sequence's KV cache to continuous-batching decode peers as
  ``FLAG_STREAM`` payloads, placed by a pricing router.

Completion is signalled off the decode path in both modes: a request is
done when its last token has been *decoded*, never at admission.

    PYTHONPATH=src python -m repro.launch.serve --steps 8
    PYTHONPATH=src python -m repro.launch.serve --mode disagg --requests 8
"""

from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import numpy as np

from repro.backend import use_compile_cache
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import (TINY, IfuncFrontend, Request, Server,
                           ServingFabric)


def make_requests(n: int, max_new: int, *, vocab: int,
                  prompt_lens: tuple[int, ...] = (8,),
                  seed: int = 0) -> list[Request]:
    """``n`` random prompts, request i of length ``prompt_lens[i % len]``."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab,
                                    size=prompt_lens[i % len(prompt_lens)],
                                    dtype=np.int32), max_new=max_new)
            for i in range(n)]


def run_host(cfg: ModelConfig, params, reqs: list[Request], *, slots: int,
             cache_len: int) -> dict[int, Request]:
    """Serve ``reqs`` on one host: ``Server`` fed by an ``IfuncFrontend``.
    Returns the finished requests by rid, as the decode path reported
    them."""
    from repro.core import Context

    server_ctx = Context("server")
    fe = IfuncFrontend(server_ctx)
    # ONE bundle across frontend transport + batcher: the final snapshot
    # shows ingest (peer/dispatcher counters) and serving side by side
    srv = Server(cfg, params, slots, cache_len, obs=fe.rt.obs)
    unsubmitted = list(reqs)
    acks = []
    done: dict[int, Request] = {}
    pending: list[Request] = []
    t0 = time.time()
    total = 0
    while unsubmitted or pending or srv.active:
        while unsubmitted:                                 # credits permitting
            fut = fe.submit(unsubmitted[0])
            if fut is None:
                break
            acks.append(fut)
            unsubmitted.pop(0)
        pending.extend(fe.server_poll())
        admitted_now = 0
        while pending and srv.admit(pending[0]):
            pending.pop(0)
            admitted_now += 1
        # completion comes off the DECODE path: tick() hands back the
        # requests whose last token just landed — only those are done
        emitted, finished = srv.tick()
        total += emitted
        for req in finished:
            done[req.rid] = req
        if admitted_now:
            print(" ", srv.wave_summary())
    dt = time.time() - t0
    acked = [f.result(timeout=10.0) for f in acks]
    assert all(a["queued"] for a in acked), acked
    assert len(done) == len(reqs), (len(done), len(reqs))
    # shutdown drain with the transport liveness floor: if the server ring
    # wedged, outstanding admission futures fail with a TransportError
    # after the deadline instead of hanging the frontend forever
    fe.rt.drain(deadline=5.0)
    stats = fe.dispatcher.per_peer_stats()["server"]
    assert stats["timed_out"] == 0, stats
    print(f"served {len(reqs)} requests ({len(acked)} acked, max queue depth "
          f"{max(a['depth'] for a in acked)}), {total} decode tokens in "
          f"{dt:.2f}s ({total / max(dt, 1e-9):.0f} tok/s, batch={slots}); "
          f"ingest: sent={stats['sent']} slim={stats['slim_sent']} "
          f"delivered={stats['delivered']} backpressure={stats['backpressure']} "
          f"replies={stats['replies']} via {stats['bytes']}B of ifunc frames "
          f"(oldest in-flight {stats['oldest_inflight_s']:.3f}s)")
    snap = srv.metrics()
    print(f"metrics: admitted={snap['counters']['serve.admitted']} "
          f"decoded={snap['counters']['serve.decoded']} "
          f"({len(snap['counters'])} counters, "
          f"{len(snap['histograms'])} histograms in the registry)")
    for rid in sorted(done)[:2]:
        r = done[rid]
        print(f"  req {r.rid}: prompt[:8]={r.prompt[:8].tolist()} "
              f"({len(r.prompt)} tokens) -> {r.out}")
    return done


def run_disagg(args, params) -> None:
    fab = ServingFabric(TINY, params, n_prefill=args.prefill,
                        n_decode=args.decode, batch_slots=args.slots,
                        cache_len=args.cache)
    reqs = make_requests(args.requests, args.steps, vocab=TINY.vocab_size)
    t0 = time.time()
    done = fab.run(reqs)
    dt = time.time() - t0
    fab.drain()
    total = sum(len(r.out) for r in done.values())
    assert fab.buffered_installs() == 0, "a KV slab arrived unstreamed"
    print(f"served {len(done)} requests across {args.prefill} prefill + "
          f"{args.decode} decode peers: {total} tokens in {dt:.2f}s "
          f"({total / max(dt, 1e-9):.0f} tok/s); "
          f"{fab.streams_landed()} KV streams landed, "
          f"{fab.buffered_installs()} buffered installs")
    snap = fab.obs.snapshot()["counters"]
    routed = snap.get("serve.router.routed", 0)
    comps = snap.get("serve.router.completions", 0)
    print(f"router: routed={routed} completions={comps} "
          f"admit_retries={snap.get('serve.router.admit_retries', 0)}")
    for rid in sorted(done)[:2]:
        r = done[rid]
        print(f"  req {r.rid}: prompt={r.prompt.tolist()} -> {r.out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("host", "disagg"), default="host")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache", type=int, default=64)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prefill", type=int, default=2)
    ap.add_argument("--decode", type=int, default=2)
    args = ap.parse_args()
    os.environ.setdefault(
        "REPRO_IFUNC_LIB_DIR",
        str(pathlib.Path(__file__).resolve().parents[3] / "ifunc_libs"))
    use_compile_cache()
    params = T.init_params(TINY, jax.random.PRNGKey(0))
    if args.mode == "host":
        run_host(TINY, params,
                 make_requests(args.requests, args.steps,
                               vocab=TINY.vocab_size),
                 slots=args.slots, cache_len=args.cache)
    else:
        run_disagg(args, params)


if __name__ == "__main__":
    main()
