"""Disaggregated serving fabric, end to end — prefill/decode peers,
streamed KV-cache migration, continuous batching.

Topology (every hop an ifunc over dispatcher rings):

* ``router``              prices decode placement (KV wire cost + live
                          admission-ring queue depth + decode occupancy)
                          and balances prefill by queue depth
* ``prefill0``/``prefill1``  prompt-processing peers: same-length prompts
                          batch into ONE forward; each sequence's KV
                          cache packs into a slab and *streams* to its
                          decode peer as a ``FLAG_STREAM`` payload
* ``decode0``/``decode1``    continuous-batching decode peers: the
                          streaming ``kv_install`` ifunc writes every
                          chunk straight into the reserved slot's landing
                          slab on arrival — zero buffered assembly — and
                          per-slot positions let sequences join and leave
                          the batch mid-wave

The demo runs the same request mix through a single-host ``Server`` and
the fabric and asserts the outputs match token for token, that every KV
migration crossed as a stream, and that the decode batch really ran
mixed-position (continuous batching, not wave batching).

    PYTHONPATH=src python examples/serving_fabric.py
"""

import os
import pathlib

os.environ.setdefault("REPRO_IFUNC_LIB_DIR",
                      str(pathlib.Path(__file__).resolve().parents[1] / "ifunc_libs"))

import jax
import numpy as np

from repro.models import transformer as T
from repro.serving import TINY, Request, Server, ServingFabric

N_PREFILL, N_DECODE = 2, 2
SLOTS, CACHE = 8, 64


def make_requests() -> list[Request]:
    """A staggered mix: three prompt lengths, three token budgets — the
    stagger is what forces mid-wave admission on the decode tier."""
    rng = np.random.default_rng(7)
    reqs = []
    for rid in range(10):
        plen = (4, 7, 11)[rid % 3]
        prompt = np.asarray(rng.integers(0, TINY.vocab_size, plen), np.int32)
        reqs.append(Request(rid, prompt, max_new=(5, 8, 12)[rid % 3]))
    return reqs


def main():
    params = T.init_params(TINY, jax.random.PRNGKey(0))

    # -- reference: single-host server (one process, serial prefill) --------
    host = Server(TINY, params, SLOTS, CACHE)
    ref: dict[int, list[int]] = {}
    pending = make_requests()
    while pending or host.active:
        while pending and host.admit(pending[0]):
            pending.pop(0)
        _, finished = host.tick()
        for r in finished:
            ref[r.rid] = list(r.out)
    print(f"single-host: {len(ref)} requests done")

    # -- the fabric ----------------------------------------------------------
    fab = ServingFabric(TINY, params, n_prefill=N_PREFILL, n_decode=N_DECODE,
                        batch_slots=SLOTS, cache_len=CACHE)
    mixed_pos = {"seen": False}

    def watch(f):
        # continuous batching in action: a decode batch whose live slots
        # sit at UNEQUAL positions (someone joined mid-wave)
        for dw in f.decode_workers:
            live = [int(dw.batcher.pos[s]) for s in dw.batcher.active]
            if len(live) >= 2 and len(set(live)) >= 2:
                mixed_pos["seen"] = True

    done = fab.run(make_requests(), tick_cb=watch)
    fab.drain()
    out = {rid: list(r.out) for rid, r in done.items()}

    streams = fab.streams_landed()
    buffered = fab.buffered_installs()
    print(f"fabric: {len(done)} requests done across {N_PREFILL} prefill + "
          f"{N_DECODE} decode peers; {streams} KV streams landed, "
          f"{buffered} buffered installs")
    snap = fab.obs.snapshot()["counters"]
    chunks = sum(dw.ctx.stats.get("stream_chunks", 0)
                 for dw in fab.decode_workers)
    batches = sum(v for k, v in snap.items() if k.endswith("prefill_batches"))
    prefills = sum(v for k, v in snap.items() if k.endswith(".prefills"))
    print(f"prefill tier: {prefills} sequences in {batches} batched forwards; "
          f"decode tier took {chunks} stream chunks")

    # every KV migration crossed as a stream, executing on arrival
    assert streams == len(done), (streams, len(done))
    assert buffered == 0, "a KV slab arrived as a buffered frame"
    # the decode batch genuinely ran mixed-position sequences
    assert mixed_pos["seen"], "decode tier never held unequal positions"
    # disaggregation changed the deployment shape, not the math
    assert out == ref, "fabric output diverged from single-host"
    for rid in sorted(out)[:3]:
        print(f"  req {rid}: {out[rid]}")
    print("SERVE_OK")


if __name__ == "__main__":
    main()
