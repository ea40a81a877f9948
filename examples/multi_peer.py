"""One source, four heterogeneous targets, one ifunc.

The unified transport layer's reason to exist: the *same* injected function
(``uvm_affine``: y = relu(x @ W), shipped as μVM code in the frame) fans
out through one :class:`Dispatcher` to

* two RDMA host peers   (RdmaFabric over the emulated NIC/rkey path),
* one device-mesh shard (DeviceMeshFabric: ppermute deposit + Pallas
  ring_poll/ifunc_vm sweep — the TPU/SmartNIC tier),
* one loopback "CSD"    (LoopbackFabric: zero-copy bus-attached target).

Credit-based flow control handles slow targets (sends beyond ring capacity
report backpressure and retry after a drain), and per-peer stats come out
of the dispatcher at the end.

Coalescing is ON (frame v2.3): cache-warm sends to host peers queue and
ship as FLAG_AGG containers (device lanes batch their own way, via
generation deposits).  A second act runs a small-message burst
(``counter_bump``) through the host peers and prints the aggregate
occupancy — the smoke's AGG_OK line asserts that coalescing actually
aggregated and that nothing was rejected or lost.

    PYTHONPATH=src python examples/multi_peer.py
"""

import os
import pathlib

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
os.environ.setdefault("REPRO_IFUNC_LIB_DIR",
                      str(pathlib.Path(__file__).resolve().parents[1] / "ifunc_libs"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Context, register_ifunc
from repro.core.codegen import deserialize_uvm
from repro.obs import Obs
from repro.transport import Dispatcher, LoopbackFabric, ProgressEngine, RdmaFabric
from repro.transport.device_fabric import DeviceMeshFabric

T, N_MSGS = 128, 6
SLOT = 128 << 10

# --- topology ---------------------------------------------------------------
source = Context("source")
handle = register_ifunc(source, "uvm_affine")

rng = np.random.default_rng(0)
W = (rng.standard_normal((T, T)) * 0.05).astype(np.float32)

from repro.parallel.sharding import make_mesh

n_dev = len(jax.devices())
mesh = make_mesh((n_dev,), ("model",))

obs = Obs("multi_peer", trace=True)       # spans on: this smoke also gates
#                                           the telemetry layer (OBS_OK)
dispatcher = Dispatcher(source, ProgressEngine(flush_threshold=8,
                                               inflight_window="trailer"),
                        obs=obs)
dispatcher.set_coalescing(True, max_subs=16)
host_args = lambda: {"externals": {"W": W}, "results": []}
for name in ("rdma_a", "rdma_b"):
    dispatcher.add_peer(name, RdmaFabric(),
                        Context(name, link_mode="remote"),
                        n_slots=4, slot_size=SLOT, target_args=host_args())
dispatcher.add_peer("csd", LoopbackFabric(),
                    Context("csd", link_mode="remote"),
                    n_slots=4, slot_size=SLOT, target_args=host_args())
uvm_prog = deserialize_uvm(handle.lib.code)
dispatcher.add_peer("tpu", DeviceMeshFabric(mesh, "model", shift=0), None,
                    n_slots=4, slot_size=SLOT, prog=uvm_prog,
                    target_args={"results": []},
                    externals=jnp.broadcast_to(jnp.asarray(W)[None, None],
                                               (n_dev, 1, T, T)))
print(f"dispatcher: {len(dispatcher.peers)} peers over "
      f"{sorted({p.fabric.kind for p in dispatcher.peers.values()})} fabrics, "
      f"{n_dev}-shard device mesh")

# --- fan the same ifunc out to every peer -----------------------------------
# send_ifunc packs each frame straight into the per-peer slab (zero-copy)
# and flips to SLIM framing per peer once a FULL delivery confirmed the
# target's code cache — μVM code crosses each wire exactly once.
payloads = rng.standard_normal((N_MSGS, 1, T, T)).astype(np.float32)
retries = delivered = 0
for i in range(N_MSGS):
    for peer in list(dispatcher.peers):
        while not dispatcher.send_ifunc(peer, handle, payloads[i]):
            retries += 1                       # ring full: let targets drain
            delivered += dispatcher.drain()
delivered += dispatcher.drain()
slim = sum(p.stats["slim_sent"] for p in dispatcher.peers.values())
print(f"fanned {N_MSGS} payloads x {len(dispatcher.peers)} peers = "
      f"{delivered} deliveries ({retries} backpressure retries, "
      f"{slim} SLIM frames)")

# --- every fabric computed the same injected function -----------------------
expect = [np.maximum(p[0] @ W, 0) for p in payloads]
for name, peer in dispatcher.peers.items():
    results = [np.asarray(r).reshape(T, T) for r in peer.target_args["results"]]
    assert len(results) == N_MSGS, (name, len(results))
    matched = set()
    for r in results:                          # device shards may reorder
        j = next(j for j, e in enumerate(expect)
                 if j not in matched and np.allclose(r, e, rtol=1e-4, atol=1e-5))
        matched.add(j)
    print(f"  {name}: {len(results)} results verified vs relu(x@W)")

# --- act two: a small-message burst through the coalescing queues -----------
# counter_bump is a host-tier verb: the first send per peer ships FULL
# (link + digest confirm), after which the burst coalesces — K invocations
# per FLAG_AGG container, one ring slot and one sweep pass each.
h_bump = register_ifunc(source, "counter_bump")
host_peers = [n for n, p in dispatcher.peers.items() if p.fabric.kind != "device"]
BURST = 48
for name in host_peers:
    dispatcher.send_ifunc(name, h_bump, b"warm")      # FULL warmup
dispatcher.drain()
burst_payloads = [bytes([i & 0x7F]) * 8 for i in range(BURST)]
for name in host_peers:
    sent = dispatcher.send_ifunc_many(name, h_bump, burst_payloads)
    assert sent == BURST, (name, sent)
dispatcher.drain()
for name in host_peers:
    peer = dispatcher.peers[name]
    count = peer.target_args.get("count", 0)
    assert count == BURST + 1, (name, count)          # warmup + burst
print(f"burst: {BURST} x {len(host_peers)} coalesced sends verified")

print("per-peer stats:")
dispatcher.print_stats()
eng = dispatcher.engine.stats
print(f"progress engine: posted={eng['posted']} completed={eng['completed']} "
      f"auto_flushes={eng['auto_flushes']}")

# aggregate occupancy: how many invocations each container actually carried
agg_frames = agg_subs = 0
for name, peer in dispatcher.peers.items():
    s = peer.stats
    if s.get("agg_sent"):
        print(f"  {name}: {s['agg_subs']} records in {s['agg_sent']} "
              f"aggregates (occupancy {s['agg_subs'] / s['agg_sent']:.1f}, "
              f"{s['coalesced']} enqueues)")
        agg_frames += s["agg_sent"]
        agg_subs += s["agg_subs"]
print(f"aggregate occupancy: {agg_subs} records / {agg_frames} containers "
      f"= {agg_subs / max(agg_frames, 1):.1f} per frame")

# CI contract: any peer reporting rejects, unrecovered NACKs (nack_lost or
# a resend that never flushed), or undrained traffic fails the smoke run
# with a nonzero exit instead of printing a green line over a red run.
failures = []
for name, peer in dispatcher.peers.items():
    s = peer.stats
    if s["rejected"]:
        failures.append(f"{name}: {s['rejected']} rejected frames")
    if s.get("nack_lost", 0):
        failures.append(f"{name}: {s['nack_lost']} unrecoverable NACKs")
    if s["nacks"] > s["resent"]:
        failures.append(f"{name}: {s['nacks']} NACKs but only "
                        f"{s['resent']} FULL retransmits")
    if peer.resend:
        failures.append(f"{name}: {len(peer.resend)} retransmits undrained")
if dispatcher.engine.outstanding():
    failures.append(f"{dispatcher.engine.outstanding()} puts never flushed")
# the coalescing contract: the burst must actually have aggregated (an
# occupancy of 1.0 means the queue never batched anything), and no queued
# record may be left behind after the drain
if agg_frames == 0 or agg_subs / agg_frames < 2.0:
    failures.append(f"no real aggregation: {agg_subs} records in "
                    f"{agg_frames} containers")
for name, peer in dispatcher.peers.items():
    leftover = sum(len(q.subs) for q in peer.coalesce.values())
    if leftover:
        failures.append(f"{name}: {leftover} coalesced records undrained")
# --- observability: metrics snapshot + Perfetto trace -----------------------
snap = obs.snapshot()
rtt = obs.rtt_hist
print(f"metrics: {len(snap['counters'])} counters, "
      f"{len(snap['histograms'])} histograms; deliver_us count={rtt.count} "
      f"p50={rtt.quantile(0.5)} p99={rtt.quantile(0.99)}")
trace_path = pathlib.Path(__file__).resolve().parent / "multi_peer_trace.json"
doc = obs.tracer.export_chrome(trace_path)
spans = obs.tracer.spans()
wire_spans = obs.tracer.spans(cat="wire")
print(f"trace: {len(doc['traceEvents'])} events ({len(spans)} spans, "
      f"{len(wire_spans)} wire) -> {trace_path.name}")

# OBS_OK contract: tracing actually recorded spans, every wire span closed
# (no orphans — a put without a poll outcome is a lifecycle bug), the
# counters saw the traffic the legacy stats saw, and a recorder ring of
# recent transport events exists for a postmortem.
if not spans:
    failures.append("obs: no spans recorded with tracing on")
if obs.tracer.open_count():
    failures.append(f"obs: {obs.tracer.open_count()} orphan (unclosed) "
                    f"spans: {[s.name for s in obs.tracer.open_spans()][:8]}")
sent_metric = sum(v for k, v in snap["counters"].items()
                  if k.startswith("peer.") and k.endswith(".sent"))
sent_stats = sum(p.stats["sent"] for p in dispatcher.peers.values())
if sent_metric != sent_stats:
    failures.append(f"obs: registry sees {sent_metric} sends, "
                    f"peer stats say {sent_stats}")
if rtt.count == 0:
    failures.append("obs: deliver_us histogram empty after a fan-out")
if len(obs.recorder) == 0:
    failures.append("obs: flight recorder empty after transport traffic")

if failures:
    print("MULTI_PEER_FAILED:" + "; ".join(failures))
    raise SystemExit(1)
print("MULTI_PEER_OK")
print("AGG_OK")
print("OBS_OK")
