"""Closed-loop chat serving through ``IfuncFrontend`` -> ``Server``.

``clients`` users each keep one request in flight with no think time (as
vLLM's ``benchmark_serving --max-concurrency``): a client sends its next
request as soon as the decode path hands back its last one.  A request is
ingested by ``IfuncFrontend.submit`` (an ``srv_enqueue`` ifunc over a
credit-flow-controlled ring), polled into the server by
``IfuncFrontend.server_poll``, prefilled and spliced into a decode slot by
``Server.admit`` (which returns its first token), and decoded by
``Server.tick`` until it has ``max_new`` tokens.

Traffic keys: ``clients``, ``prompt_mix`` (prompt length -> share; each
length is one prefill shape), ``max_new`` ([lo, hi], spread evenly),
``block`` (requests per block: every block holds the mix's exact shares
and evenly spread output lengths, in an order drawn from the seed, so any
prefix of the stream carries the same work), ``ingest_slots``,
``ingest_slot_bytes``, ``sample`` (finished requests compared, the
longest among them), ``trace_start_s`` and ``trace_seconds``.

The configuration names its plain reference (``"reference": <name>``,
``bench/reference/<name>.py``), which draws the weights from the seed,
maps them onto the program's model, and computes the numbers compared; a
decoder of another architecture that the program serves through
``Server`` adds a reference file and a configuration, not a loop.

The clients' first requests fill every slot during set-up, so the window
opens on the steady state; the requests sent in the window are the ones
counted.  After the window (and the first token of every request sent in
it), the program's state is freed and a sample of the finished requests,
drawn from the seed, is compared with the reference: over every served
token of the sample, the gap by which its reference logit lies below the
reference's best (``mean_logit_gap``, ``max_logit_gap``) and the share of
tokens that are not its best (``not_best_share``).  Each of them that the
configuration's ``limits`` names is compared.  With ``run.control`` the
control's tokens at the same positions take the served tokens' place in
that comparison.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import Check, Outcome, Run, load_module, percentile

DRAIN_S = 60.0
WARM_RID = 0xFFFF0000      # request ids of the warm-up (the codec's are u32)


def requests(tr: dict, seed: int, vocab: int, n_blocks: int):
    """``n_blocks`` blocks of (prompt, max_new), each block the mix's exact
    shares in an order drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    B = tr["block"]
    lens = []
    for p, share in sorted(tr["prompt_mix"].items(), key=lambda kv: int(kv[0])):
        lens += [int(p)] * round(share * B)
    if len(lens) != B:
        raise ValueError(f"prompt_mix shares do not split a block of {B}")
    lo, hi = tr["max_new"]
    outs = np.rint(np.linspace(lo, hi, B)).astype(int).tolist()
    reqs = []
    for _ in range(n_blocks):
        pl, ol = rng.permutation(lens), rng.permutation(outs)
        for p, o in zip(pl, ol):
            reqs.append((rng.integers(0, vocab, size=int(p), dtype=np.int32),
                         int(o)))
    return reqs


class _Loop:
    def __init__(self, run: Run, fe, srv, reqs):
        self.run, self.fe, self.srv, self.reqs = run, fe, srv, reqs
        self.next = 0
        self.ready: list = []            # (t_due, rid) of clients to send
        self.sent: dict = {}             # rid -> t_due
        self.first: dict = {}            # rid -> t of the first token
        self.pending: list = []          # arrived, not yet admitted
        self.acks: list = []
        self.finished: dict = {}         # rid -> (Request, t_done)
        self.tokens: list = []           # (t, n) tokens produced
        # running counts the traced window reads at both ends
        self.c = dict.fromkeys(("admitted", "prefill_tokens", "prefill_pairs",
                                "tick_tokens", "ctx_tokens", "ticks"), 0)

    def client(self, t: float) -> None:
        self.ready.append((t, self.next))
        self.next += 1

    def turn(self, sending: bool = True) -> None:
        from repro.serving import Request

        fe, srv, spans = self.fe, self.srv, self.run.spans
        while sending and self.ready:
            t_due, rid = self.ready[0]
            prompt, max_new = self.reqs[rid % len(self.reqs)]
            with spans("bench.ingest", 1):
                fut = fe.submit(Request(rid, prompt, max_new))
            if fut is None:
                break
            self.ready.pop(0)
            self.sent[rid] = t_due
            self.acks.append(fut)
        with spans("bench.server_poll"):
            self.pending.extend(fe.server_poll(max_msgs=srv.B))
        while self.pending:
            with spans("bench.admit", 1):
                ok = srv.admit(self.pending[0])
            if not ok:
                break
            t = time.monotonic()
            req = self.pending.pop(0)
            self.first[req.rid] = t
            self.tokens.append((t, 1))
            P, c = len(req.prompt), self.c
            c["admitted"] += 1
            c["prefill_tokens"] += P
            c["prefill_pairs"] += P * (P + 1) // 2
        if srv.active:
            c = self.c
            c["ctx_tokens"] += sum(len(r.prompt) + len(r.out)
                                   for r in srv.active.values())
            with spans("bench.tick", 1):
                emitted, done = srv.tick()
            t = time.monotonic()
            c["ticks"] += 1
            c["tick_tokens"] += emitted
            self.tokens.append((t, emitted))
            for req in done:
                self.finished[req.rid] = (req, t)
                if sending:
                    self.client(t)


def _warm(fe, srv, tr: dict, vocab: int) -> None:
    """One request of every prompt length through the whole path, with two
    tokens each: compiles each prefill shape, the splice and the decode
    step, and confirms ``srv_enqueue`` in the server's link cache."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    todo = [Request(WARM_RID + i, rng.integers(0, vocab, int(p), dtype=np.int32), 2)
            for i, p in enumerate(sorted(tr["prompt_mix"], key=int))]
    want = {r.rid for r in todo}
    pending, done, acks = [], set(), []
    deadline = time.monotonic() + 1200.0
    while want - done and time.monotonic() < deadline:
        while todo and (f := fe.submit(todo[0])) is not None:
            acks.append(f)
            todo.pop(0)
        pending.extend(fe.server_poll(max_msgs=srv.B))
        while pending and srv.admit(pending[0]):
            pending.pop(0)
        done |= {r.rid for r in srv.tick()[1]}
    if want - done:
        raise RuntimeError(f"warm-up: requests {sorted(want - done)} unserved")


def run(run: Run) -> Outcome:
    from repro.core import Context
    from repro.models.config import ModelConfig
    from repro.serving import IfuncFrontend, Server

    cfg, tr = run.cell.config, run.cell.traffic
    REF = load_module("reference", cfg["reference"])
    d = REF.Dims.of(cfg)
    mcfg = ModelConfig(**REF.program_config(cfg))
    params = REF.program_params(d, REF.init_weights(d, run.seed))
    fe = IfuncFrontend(Context("server"), n_slots=tr["ingest_slots"],
                       slot_size=tr["ingest_slot_bytes"])
    srv = Server(mcfg, params, cfg["decode_slots"], cfg["cache_len"],
                 obs=fe.rt.obs)
    reqs = requests(tr, run.seed, d.vocab, tr["blocks"])
    _warm(fe, srv, tr, d.vocab)

    lp = _Loop(run, fe, srv, reqs)
    tw = run.trace
    if tw is not None:
        for k in lp.c:
            tw.counter(k, lambda k=k: lp.c[k])

    # the clients' first requests fill every slot before the window opens,
    # so it measures the steady state (set-up the traffic needs)
    t_fill = time.monotonic()
    for _ in range(tr["clients"]):
        lp.client(t_fill)
    while lp.ready or lp.pending or len(srv.active) < min(srv.B,
                                                          tr["clients"]):
        lp.turn()
        if time.monotonic() - t_fill > 1200.0:
            raise RuntimeError("set-up: the clients' first requests were "
                               "not all admitted")

    run.setup_done()
    t0 = time.monotonic()
    t_end = t0 + run.seconds
    while (now := time.monotonic()) < t_end:
        if tw is not None:
            tw.poll(now - t0)
        lp.turn()
    if tw is not None:
        tw.close()
    run.window_done()
    sent = {r: t for r, t in lp.sent.items() if t >= t0}
    limit = time.monotonic() + DRAIN_S
    while (set(sent) - set(lp.first)) and time.monotonic() < limit:
        lp.turn(sending=False)
    run.read_memory_peak()
    ack_failed = sum(1 for f in lp.acks
                     if not f.done() or f.exception() is not None
                     or not f.result().get("queued"))

    ttft = [(lp.first[r] - t) * 1e3 for r, t in sent.items() if r in lp.first]
    n_tok = sum(n for t, n in lp.tokens if t0 <= t <= t_end)
    metrics = {"output_tokens_per_s": n_tok / run.seconds}
    if ttft:
        metrics["ttft_p95_ms"] = percentile(ttft, 95)
    done = [(req, t) for req, t in lp.finished.values() if t <= t_end]
    never = len(sent) - len(ttft)

    # free the program's state, then compare a sample with the reference
    del lp, srv, fe, params
    gc.collect()
    rng = np.random.default_rng([run.seed, 3])
    done.sort(key=lambda rt: rt[0].rid)
    pick: list = []
    if done:
        longest = max(range(len(done)),
                      key=lambda i: len(done[i][0].prompt) + len(done[i][0].out))
        rest = [i for i in range(len(done)) if i != longest]
        k = min(tr["sample"] - 1, len(rest))
        pick = [longest] + sorted(rng.choice(rest, k, replace=False).tolist())
    checks, served, got = [], 0, {}
    if pick:
        w = REF.init_weights(d, run.seed)
        width = max(int(p) for p in tr["prompt_mix"]) + tr["max_new"][1]
        gaps = [REF.logit_gaps(d, w, done[i][0].prompt, done[i][0].out,
                               width, control=run.control) for i in pick]
        del w
        served = sum(len(done[i][0].out) for i in pick)
        got = {"program": REF.compared([g[0] for g in gaps], served)}
        if run.control:
            got["control"] = REF.compared([g[1] for g in gaps], served)
        values = got["control" if run.control else "program"]
        checks = [Check(k, values[k], lim)
                  for k, lim in cfg["limits"].items()]
    notes = [f"{len(sent)} requests sent in the window, {len(done)} finished "
             f"in it, {len(pick)} compared ({served} served tokens), "
             f"{ack_failed} ingest failures, {never} without a first token"]
    notes += [f"{who}: {v!r}" for who, v in got.items()]
    return Outcome(len(sent), ack_failed + never, metrics, checks,
                   notes=notes)
