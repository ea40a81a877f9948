"""Drive the main path once on TPU chips and check every result.

    python chip_smoke.py              # one chip: phases a, b and c
    python chip_smoke.py --chips 4    # four chips: phases a and b, shift=1

Each phase goes through the entry points a user calls and checks what comes
out against a plain reference:

a. Singleton device lane through ``TaskRuntime``.  A ``DeviceMeshFabric``
   runs the ``uvm_affine`` ifunc (y = relu(x @ W), W in the externals
   table) with 64 slots of 8 tiles per chip: 512 KiB frames, a 32 MiB ring.
   Four ring-fulls of ``rt.submit`` futures wrap the ring four times, and
   every result is compared with numpy.
b. Aggregate device lane through ``Dispatcher.send_ifunc_many`` with
   coalescing on: 64 sub-records of one tile per container, 16 slots per
   chip (a 64 MiB ring).  One ring-full of invocations, then one more
   container whose sub-record descriptor is poisoned before the flush.
   Every OK result is compared with numpy; the poisoned sub-record must
   end as an error and its siblings must be unharmed.
c. Serving: ``Server`` + ``IfuncFrontend`` as ``launch/serve.py`` runs
   them, at the published widths of SmolLM-360M with random bf16
   parameters from the seed, 8 slots and a 1024-token cache.  16 requests
   with prompts of 128 and 512 tokens ask for 32 tokens each; every one
   must complete off the decode path.  Two of the prompts are then served
   again in float32 and their greedy tokens compared one for one with a
   no-cache reference over the same parameters.

With ``--chips 4`` the mesh spans four chips and every deposit goes one
shard to the right (``shift=1``): a frame staged on chip s executes on chip
s+1, and the reply demux maps the result back to the future that staged
it.  A future that holds the result of its own payload therefore shows
that its neighbour ran it.

Tolerances: μVM results against numpy at rtol 1e-4, atol 1e-5, the CPU
tests' bounds (the kernel's matmul runs at ``Precision.HIGHEST``).  Served
tokens against the reference: equal, with both sides in float32 under
``jax.default_matmul_precision("highest")``, since the chip's default f32
matmul rounds its inputs to bf16.

The lines before the last say what each phase checked, its sizes, its
set-up and compile wall time and the peak device bytes: bring-up facts,
not benchmark results.  The script needs a TPU: without one it fails and
prints no result.  Its last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
T = 128                     # μVM tile edge (core.codegen.UVM_TILE)
RTOL, ATOL = 1e-4, 1e-5
TIMEOUT_S = 600.0           # per future, compile of the first sweep included


class SmokeError(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def peak_bytes(devs) -> list:
    """``peak_bytes_in_use`` per device, where the backend reports it."""
    out = []
    for d in devs:
        st = d.memory_stats()
        out.append(st.get("peak_bytes_in_use") if st else None)
    return out


def _affine_lane(devs):
    """Mesh over ``devs``, the uvm_affine handle, its program and a W."""
    import numpy as np

    from repro.core import Context, register_ifunc
    from repro.core.codegen import deserialize_uvm
    from repro.parallel.sharding import make_mesh

    mesh = make_mesh((len(devs),), ("model",), devices=devs)
    src = Context("src")
    h = register_ifunc(src, "uvm_affine")
    rng = np.random.default_rng(SEED)
    W = (rng.standard_normal((T, T)) / np.sqrt(T)).astype(np.float32)
    ext = np.broadcast_to(W, (len(devs), 1, T, T))
    return mesh, src, h, deserialize_uvm(h.lib.code), W, ext, rng


def _warm(mb) -> float:
    """Compile the lane's deposit and sweep on an empty ring (no frame is
    written, nothing is kept); returns the wall time.  The sweep must hold
    Mosaic kernels: a Pallas interpreter would lower to plain XLA."""
    import jax
    import numpy as np

    check("tpu_custom_call" in mb._sweep.lower(mb._mb, mb.externals).as_text(),
          "the device sweep holds no compiled Pallas kernel")
    t0 = time.perf_counter()
    zeros = np.zeros((mb.n_shards, mb.n_slots_per_shard, mb.slot_words),
                     np.uint32)
    jax.block_until_ready(mb._deposit(mb._mb, jax.device_put(
        zeros, mb._sharding), shift=mb.shift))
    jax.block_until_ready(mb._sweep(mb._mb, mb.externals))
    return time.perf_counter() - t0


def phase_singleton(devs, *, shift: int, n_slots: int = 64, n_tiles: int = 8,
                    wraps: int = 4) -> str:
    import numpy as np

    from repro.tasks import TaskRuntime
    from repro.transport import Dispatcher, ProgressEngine
    from repro.transport.device_fabric import DeviceMeshFabric

    t0 = time.perf_counter()
    mesh, src, h, prog, W, ext, rng = _affine_lane(devs)
    rt = TaskRuntime(src, Dispatcher(src, ProgressEngine(
        inflight_window="trailer")), default_timeout=TIMEOUT_S)
    frame_bytes = n_tiles * T * T * 4
    peer = rt.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=shift),
                       None, n_slots=n_slots,
                       slot_size=frame_bytes + (64 << 10), prog=prog,
                       n_tiles=n_tiles, externals=ext)
    mb = peer.rings[0].mailbox
    n = wraps * mb.n_slots
    xs = rng.standard_normal((n, n_tiles, T, T), dtype=np.float32)
    setup_s = time.perf_counter() - t0
    compile_s = _warm(mb)

    t0 = time.perf_counter()
    futs = [rt.submit("tpu", h, x) for x in xs]
    got = np.stack([np.asarray(f.result()) for f in futs])
    rt.drain()
    run_s = time.perf_counter() - t0
    check(rt.pending() == 0, f"{rt.pending()} futures still pending")
    want = np.maximum(xs @ W, 0)
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    return (f"phase a (singleton lane, TaskRuntime): {n} futures = {wraps} "
            f"ring wraps, each result equal to numpy relu(x @ W) "
            f"(max abs err {err:.3g}); {len(devs)} chip(s), shift={shift}, "
            f"{n_slots} slots x {n_tiles} tiles per chip "
            f"({mb.slot_words * 4 * n_slots / 2**20:.0f} MiB ring per chip); "
            f"set-up {setup_s:.1f}s, compile {compile_s:.1f}s, "
            f"traffic {run_s:.1f}s; peak device bytes {peak_bytes(devs)}")


def phase_aggregate(devs, *, shift: int, agg_k: int = 64, n_slots: int = 16,
                    poison: int = 1) -> str:
    import numpy as np

    from repro.kernels.ring_poll import HDR_WORDS
    from repro.transport import Dispatcher, ProgressEngine
    from repro.transport.device_fabric import DeviceMeshFabric

    t0 = time.perf_counter()
    mesh, src, h, prog, W, ext, rng = _affine_lane(devs)
    d = Dispatcher(src, ProgressEngine(inflight_window="trailer"))
    d.set_coalescing(True, max_subs=agg_k, max_sub_bytes=128 << 10)
    sub_bytes = T * T * 4
    peer = d.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=shift),
                      None, n_slots=n_slots,
                      slot_size=agg_k * sub_bytes + (1 << 20), prog=prog,
                      externals=ext, agg_k=agg_k, prog_name=h.lib.name)
    mb = peer.rings[0].mailbox
    replies: dict[int, tuple] = {}

    def route(corr, name, value, is_err, decoded):
        check(corr not in replies, f"second reply for corr {corr}")
        replies[corr] = (value, is_err)
    d.reply_router = route

    n = agg_k * mb.n_slots
    xs = rng.standard_normal((n + agg_k, 1, T, T), dtype=np.float32)
    corrs = list(range(1, n + agg_k + 1))
    setup_s = time.perf_counter() - t0
    compile_s = _warm(mb)

    t0 = time.perf_counter()
    sent = stalls = 0
    while sent < n:
        k = d.send_ifunc_many("tpu", h, xs[sent:n], corr_ids=corrs[sent:n])
        sent += k
        if k == 0:
            stalls += 1
            check(stalls < 1000, f"lane refused sends at {sent}/{n}")
            d.drain()
    d.drain()
    check(len(replies) == n, f"{len(replies)} replies for {n} invocations")

    # one more container, one sub-record poisoned while it is staged
    check(d.send_ifunc_many("tpu", h, xs[n:], corr_ids=corrs[n:]) == agg_k,
          "poison container not accepted whole")
    rows = np.argwhere(mb._staged[:, :, 0] != 0) if mb._staged is not None \
        else np.zeros((0, 2))
    check(len(rows) == 1, f"expected one staged container, found {len(rows)}")
    shard, slot = rows[0]
    mb._staged[shard, slot, HDR_WORDS + 2 * poison + 1] ^= 1
    d.drain()
    run_s = time.perf_counter() - t0

    check(len(replies) == n + agg_k,
          f"{len(replies)} replies for {n + agg_k} invocations")
    bad_val, bad_err = replies[corrs[n + poison]]
    check(bad_err and "poisoned" in str(bad_val),
          f"poisoned sub-record resolved as {bad_val!r}")
    ok = [i for i in range(n + agg_k) if i != n + poison]
    for i in ok:
        check(not replies[corrs[i]][1], f"invocation {i} failed: "
              f"{replies[corrs[i]][0]!r}")
    got = np.stack([np.asarray(replies[corrs[i]][0]) for i in ok])
    want = np.maximum(xs[ok] @ W, 0)
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    check(peer.stats["rejected"] == 1,
          f"rejected={peer.stats['rejected']}, want the poisoned one only")
    return (f"phase b (aggregate lane, send_ifunc_many): {n} invocations in "
            f"{n // agg_k} containers + 1 container with sub {poison} "
            f"poisoned -> ERR, its {agg_k - 1} siblings and every other "
            f"result equal to numpy (max abs err {err:.3g}); "
            f"{len(devs)} chip(s), shift={shift}, agg_k={agg_k}, "
            f"{n_slots} slots per chip "
            f"({mb.slot_words * 4 * n_slots / 2**20:.0f} MiB ring per chip); "
            f"set-up {setup_s:.1f}s, compile {compile_s:.1f}s, "
            f"traffic {run_s:.1f}s; peak device bytes {peak_bytes(devs)}")


def greedy_reference(cfg, params, prompt, n_new: int) -> list[int]:
    """Greedy decoding by a full forward over the whole sequence at every
    step, no cache.  Causal attention lets one padded buffer serve every
    step: positions past the current one never reach the token read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as TF

    @jax.jit
    def next_token(p, toks, at):
        logits = TF.forward(p, {"tokens": toks}, cfg, mode="train")[0]
        return jnp.argmax(logits[0, at])

    buf = np.zeros((1, len(prompt) + n_new), np.int32)
    buf[0, :len(prompt)] = prompt
    out = []
    for i in range(n_new):
        at = len(prompt) + i
        tok = int(next_token(params, buf, at - 1))
        out.append(tok)
        buf[0, at] = tok
    return out


def phase_serving(devs, cfg, *, slots: int = 8, cache_len: int = 1024,
                  n_requests: int = 16, prompt_lens=(128, 512),
                  max_new: int = 32) -> str:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import make_requests, run_host
    from repro.models import transformer as TF
    from repro.serving import Request

    t0 = time.perf_counter()
    params = jax.jit(TF.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    reqs = make_requests(n_requests, max_new, vocab=cfg.vocab_size,
                         prompt_lens=prompt_lens, seed=SEED)
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    done = run_host(cfg, params, reqs, slots=slots, cache_len=cache_len)
    serve_s = time.perf_counter() - t0
    check(sorted(done) == [r.rid for r in reqs],
          f"finished {sorted(done)}, want every rid")
    for r in done.values():
        check(len(r.out) == max_new,
              f"request {r.rid} ended with {len(r.out)} tokens")

    # the same prompts in float32 against the no-cache reference
    t0 = time.perf_counter()
    cfg32 = cfg.with_(dtype="float32", param_dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    picks = [next(r for r in reqs if len(r.prompt) == n)
             for n in prompt_lens[:2]]
    with jax.default_matmul_precision("highest"):
        served = run_host(cfg32, params32,
                          [Request(r.rid, r.prompt, max_new) for r in picks],
                          slots=slots, cache_len=cache_len)
        for r in picks:
            want = greedy_reference(cfg32, params32, r.prompt, max_new)
            check(served[r.rid].out == want,
                  f"request {r.rid}: served {served[r.rid].out} != "
                  f"reference {want}")
    ref_s = time.perf_counter() - t0
    return (f"phase c (serving, Server + IfuncFrontend): {n_requests} "
            f"requests, prompts {list(prompt_lens)}, {max_new} new tokens "
            f"each, all finished off the decode path; greedy tokens of "
            f"prompts {[len(r.prompt) for r in picks]} served in float32 "
            f"equal a no-cache float32 reference; {cfg.name} "
            f"({cfg.num_layers} layers, d_model {cfg.d_model}, vocab "
            f"{cfg.vocab_size}), {slots} slots, cache_len {cache_len}; "
            f"set-up {setup_s:.1f}s, bf16 serving incl. compile "
            f"{serve_s:.1f}s, f32 check incl. compile {ref_s:.1f}s; "
            f"peak device bytes {peak_bytes(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip device lanes (shift=1)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["REPRO_IFUNC_LIB_DIR"] = str(ROOT / "ifunc_libs")

    from repro.backend import use_compile_cache

    cache = use_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    devs = jax.devices()[:args.chips]
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    print(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, compile cache "
          f"{cache}", flush=True)

    shift = 1 if args.chips > 1 else 0
    print(phase_singleton(devs, shift=shift), flush=True)
    print(phase_aggregate(devs, shift=shift), flush=True)
    if args.chips == 1:
        from repro.configs import get_config

        print(phase_serving(devs, get_config("smollm_360m")), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
