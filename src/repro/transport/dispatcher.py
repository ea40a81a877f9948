"""Multi-peer ifunc dispatcher: N peers x M rings, credit-based flow
control, per-peer backpressure, and a fairness-aware poll loop.

This replaces the single-slot ``poll_ring`` pattern: instead of one source
spinning on one ring, a :class:`Dispatcher` owns any number of
:class:`Peer` s — each a (fabric, channel(s), mailbox(s), target context)
bundle on *any* backend (RDMA host, device mesh, loopback/CSD) — and

* ``send`` consumes a credit (one free ring slot) or reports backpressure
  instead of silently overwriting unconsumed frames;
* credits return as the target's sweep advances its mailbox ``consumed``
  counter (the credit-return counter a real target writes back);
* ``poll`` drains mailboxes deficit-round-robin, starting one past the
  ring served first last time, so a chatty peer cannot starve the rest;
* all sends go through a shared :class:`ProgressEngine`, so batching,
  in-flight windows, and completions are uniform across fabrics.

The dispatcher also owns the *cached-invocation fast path* (paper §3.4):

* every frame is packed straight into the engine's per-peer slab cell for
  its ring slot (``pack_frame_into``/``seal_frame``) — the send path
  allocates no per-message buffers;
* a peer's first delivery of an ifunc ships a FULL frame; once the
  delivery is confirmed (the target's link cache provably holds the code
  digest) subsequent sends of the same handle flip to SLIM frames — header
  + payload, code elided;
* a SLIM frame that misses the target's cache (eviction, restart) comes
  back as ``NACK_UNCACHED``: the dispatcher rebuilds the FULL frame from
  the handle's library + the slab-resident payload and retransmits it
  transparently, ahead of any newer traffic to that peer;
* device-mesh lanes are always SLIM-eligible — the μVM program is bound at
  mailbox-open time, so code words never need depositing over the ICI.

And the *result-return path* (the task runtime's wire, see ``repro.tasks``):

* a request carrying a nonzero ``corr_id`` asks for the ifunc's output
  back; host peers get a *reply ring* — a source-owned mailbox the target
  writes ``FLAG_REPLY`` frames into — attached via ``attach_reply_ring``;
* the poll loop, when it executes a corr-carrying request at a host peer,
  captures the ifunc's ``target_args["result"]`` (or the exception it
  raised — the slot is consumed, not wedged) and posts the encoded value
  as a reply frame with the same corr_id; ``poll_replies`` drains reply
  rings and hands ``(corr_id, value)`` to the registered ``reply_router``;
* device-mesh lanes have no reverse ring: the sweep's READY results *are*
  the replies — the dispatcher correlates them to corr-ids by the
  (shard, slot) coordinates each send staged into and routes them through
  the same ``reply_router``;
* encoding is delegated to a pluggable ``reply_codec`` (the task layer's
  wire module) so the transport stays value-format-agnostic.

Plus the flow layer's *continuation frames* and the *liveness floor*:

* ``send``/``send_ifunc`` carry an optional packed continuation
  descriptor (frame v2.2 ``cont`` section, host fabrics only); the
  on-the-fly SLIM repack and the NACK FULL-rebuild both preserve it, so
  a retransmitted hop never loses its route;
* every tracked in-flight frame is timestamped: ``per_peer_stats()``
  surfaces the oldest age per peer, and ``drain(deadline=...)`` fails
  the futures of frames stuck at a wedged peer (``fail_inflight``)
  instead of letting them hang forever.

And *coalesced dispatch* (frame v2.3 ``FLAG_AGG``), the small-message
rate lever:

* with :meth:`set_coalescing` enabled, a cache-warm ``send_ifunc`` to a
  host peer does not claim a ring slot — it lands in that peer's
  per-(peer, ring) coalescing queue.  The queue flushes into ONE
  aggregate container (one put, one slot, one credit, one trailer spin
  for K invocations) on any of: the slot byte budget filling, the
  sub-record cap, an explicit ``flush``/``drain``, or the age bound
  ``agg_max_age`` checked each poll.  A queue holding a single record
  flushes as a plain SLIM singleton — the latency path never regresses;
* the target decodes the whole container in one ``poll_ifunc`` pass and
  reports per-sub-record statuses (``Mailbox.last_agg``): a sub-record
  whose digest was evicted NACKs *individually* and is rebuilt as a FULL
  singleton retransmit — its executed siblings are never replayed — on
  the same quiescence-gated resend queue per-peer FIFO already rides;
* replies coalesce symmetrically: the corr-carrying records of one
  aggregate post their results as ONE ``FLAG_AGG|FLAG_REPLY`` frame into
  the reply ring, and ``poll_replies`` demuxes it back per corr_id;
* unbudgeted polls (``drain``) sweep a whole ring's worth of ready slots
  per lane visit instead of one message per poll-loop round — budgeted
  polls keep the historical one-per-lane-per-round fairness contract;
* device-mesh lanes never coalesce: the deposit/sweep pipeline already
  batches generation-wide (aggregates are host-tier by construction).

And *streamed large payloads* (frame v2.5 ``FLAG_STREAM``), the
64KiB-cliff killer on the other end of the size spectrum:

* with :meth:`set_streaming` enabled, a payload larger than the stream
  threshold no longer store-and-forwards through one slot-bounded frame —
  :meth:`send_stream` opens a FLAG_STREAM frame (header + descriptor +
  ``window x cell`` chunk cells) in ONE ring slot and the dispatcher's
  chunk pump (:meth:`poll` / :meth:`drain` / :meth:`flush`) posts the
  payload as pipelined per-chunk puts, each sealed by its own delivery
  barrier, at most ``window`` chunks ahead of the target's consume
  cursor (``Mailbox.stream_consumed``);
* ``send_ifunc`` / coalesced enqueues route oversized payloads into the
  stream path automatically (host, non-striped peers — a stream would
  wedge a striped rotation);
* per-peer wire codecs (``add_peer(codec=...)``) transform chunk bytes
  in flight — a chunk that doesn't shrink ships raw, so negotiation
  never inflates the wire;
* SLIM streams NACK at the descriptor exactly like singletons: the
  rebuild re-opens the stream FULL from chunk 0 under a fresh nonce (no
  chunks executed — the miss surfaces before any chunk is consumed), on
  the same quiescence-gated resend queue; ``fail_inflight`` / ``drain
  (deadline=)`` resolve a half-arrived stream's future like any tracked
  frame and kill its pump.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace

from repro.core import frame as F
from repro.obs import NULL_SCOPE, Obs
from repro.transport import codec as WC
from repro.transport.fabric import Fabric, TransportError
from repro.transport.progress import ProgressEngine

DEFAULT_SLOT_SIZE = 64 << 10
DEFAULT_N_SLOTS = 8

#: the full per-peer stats schema, seeded at construction (and by
#: ``Peer.reset_stats``) so ``per_peer_stats()`` always returns the same
#: keys — increment sites do plain ``+= 1``, never ``.get(k, 0)``
_PEER_STAT_KEYS = (
    "sent", "bytes", "delivered", "rejected", "backpressure",
    "inflight_polls", "slim_sent", "nacks", "resent", "replies", "errors",
    "coalesced", "agg_sent", "agg_subs", "agg_replies", "agg_harvest_lost",
    "nack_lost", "reply_rejects", "streams", "stream_chunks", "timed_out",
    "fenced_orphans", "dropped_puts")

_API = None      # repro.core.api, imported lazily (it imports codegen —
#                  the transport layer must stay importable without it)
#                  and memoized: poll/sweep must not pay the import
#                  machinery per call


def _api():
    global _API
    if _API is None:
        from repro.core import api
        _API = api
    return _API


@dataclass
class _TxRec:
    """Source-side record of one in-flight frame (for digest confirmation,
    NACK retransmission, reply correlation, and liveness tracking).
    ``subs`` non-None marks an aggregate container: the listed
    :class:`_PendingSub` records are what the frame actually carries."""

    name: str
    digest: bytes
    handle: object          # IfuncHandle (None for raw-frame sends)
    slim: bool
    corr_id: int = 0
    sent_at: float = field(default_factory=time.monotonic)
    subs: list | None = None
    stream: object = None   # _StreamTx when this slot holds a FLAG_STREAM
    #                         frame (the pump's source-side state)
    span: object = None     # open obs wire span (tracing runs only): put ->
    #                         delivery confirmation / NACK / reject


@dataclass(slots=True)
class _StreamTx:
    """Source-side state of one streamed payload: the stable payload view
    (zero-copy contract — the caller must not mutate it until the stream
    resolves), the committed chunk geometry, and the pump cursor.  Lives
    in ``Dispatcher._active_streams`` while chunks remain to post; the
    slot's :class:`_TxRec` points back here so poll outcomes (OK /
    REJECTED / NACK) can stop or restart the pump."""

    handle: object
    payload: memoryview
    desc: F.StreamDesc
    codec: object            # negotiated wire codec (None -> raw)
    peer: "Peer"
    lane: "RingState"
    abs_slot: int
    cells_base: int          # slot offset of cell 0 (header + code + desc)
    corr_id: int = 0
    future: object = None
    next_send: int = 0       # chunks posted so far (the pump cursor)
    dead: bool = False       # NACKed/rejected/failed: pump must not touch
    #                          the slot again (a restart revives the tx)


class _StreamResend:
    """Queued FULL re-open of a NACKed SLIM stream.  Rides ``peer.resend``
    next to IfuncMsg retransmits (the type check in ``_flush_resends``
    dispatches); ``corr_id`` mirrors the tx so the fail-path's queued-
    retransmit drop resolves its future like any other entry."""

    __slots__ = ("tx", "corr_id")

    def __init__(self, tx: _StreamTx):
        self.tx = tx
        self.corr_id = tx.corr_id


@dataclass(slots=True)
class _PendingSub:
    """One coalesced invocation awaiting (or riding) an aggregate: the
    materialized payload plus everything a FULL-singleton rebuild needs.
    name/kind/digest are copied out of the handle's library at enqueue so
    the pack loop reads plain slots — and the attribute protocol matches
    :class:`frame.AggSub`, so ``seal_agg_frame`` packs these directly
    (no intermediate wire-object per record)."""

    handle: object
    name: str
    kind: object
    digest: bytes
    payload: bytes
    corr_id: int
    cont: bytes | None
    future: object
    enq_at: float
    err: bool = False       # request records never carry the reply-err bit


class _CoalesceQ:
    """One (peer, ring)'s pending sub-records with an exact running byte
    count of the aggregate frame they would pack into."""

    __slots__ = ("subs", "names", "bytes")

    #: header + sub/name counts + aggregate signal + frame trailer
    BASE = F.HEADER_LEN + 4 + 4 + F.TRAILER_LEN

    def __init__(self):
        self.subs: list[_PendingSub] = []
        self.names: set[str] = set()
        self.bytes = self.BASE

    def would_take(self, sub: _PendingSub) -> int:
        extra = (F.AGG_SUB_OVERHEAD + len(sub.payload)
                 + (0 if sub.cont is None else len(sub.cont)))
        if sub.name not in self.names:
            # ifunc names are policy-constrained ASCII: len == byte length
            extra += 1 + len(sub.name)
        return self.bytes + extra

    def add(self, sub: _PendingSub) -> None:
        self.bytes = self.would_take(sub)
        self.names.add(sub.name)
        self.subs.append(sub)


@dataclass
class RingState:
    """One (mailbox, channel) lane of a peer."""

    mailbox: object
    channel: object
    tail: int = 0            # source-side produce index
    inflight: dict = field(default_factory=dict)   # abs slot -> _TxRec
    corr_by_coords: dict = field(default_factory=dict)  # device lanes:
    #                                    (shard, slot) -> (corr_id, sent_at)
    #                                    awaiting a sweep result
    agg_by_coords: dict = field(default_factory=dict)  # device lanes:
    #                                    (shard, slot) -> _TxRec of a staged
    #                                    aggregate container (device frames
    #                                    have no slot->inflight tracking; the
    #                                    sweep reports by coordinates)

    @property
    def credits(self) -> int:
        return self.mailbox.n_slots - (self.tail - self.mailbox.consumed)


@dataclass
class Peer:
    name: str
    fabric: Fabric
    target_ctx: object
    target_args: dict
    rings: list[RingState] = field(default_factory=list)
    cached: set = field(default_factory=set)       # digests confirmed cached
    resend: deque = field(default_factory=deque)   # FULL msgs queued post-NACK
    coalesce: dict = field(default_factory=dict)   # ring key -> _CoalesceQ of
    #                                  sub-records awaiting an aggregate flush
    stripe: bool = False           # multi-ring striping: posts rotate across
    #                                  rings instead of greedy credit-max
    stripe_tx: int = 0             # next ring to post into (mod len(rings))
    stripe_rx: int = 0             # next ring to consume from — strict TX==RX
    #                                  rotation keeps per-peer FIFO across M
    #                                  rings with ONE demux (the reply ring
    #                                  and resend queue stay per-peer)
    codec: object = None           # negotiated wire codec for streamed sends
    #                                  (frame v2.5; None -> raw chunks)
    reply_mailbox: object = None   # source-owned ring the target replies into
    reply_channel: object = None   # target->source path into it
    reply_tail: int = 0            # target-side produce index for replies
    fence: int = 0                 # generation fence: replies whose corr was
    #                                  allocated under an earlier fleet
    #                                  generation (corr_gen < fence) are
    #                                  resurrection attempts from this peer's
    #                                  previous life — dropped + counted as
    #                                  fenced_orphans.  Stamped at
    #                                  re-admission; 0 = never fenced.
    stats: dict = field(
        default_factory=lambda: dict.fromkeys(_PEER_STAT_KEYS, 0))

    def reset_stats(self) -> None:
        """Zero every counter in place (the dict identity is aliased into
        the obs registry and shared with callers — never replace it)."""
        for k in _PEER_STAT_KEYS:
            self.stats[k] = 0

    @property
    def credits(self) -> int:
        return sum(r.credits for r in self.rings)

    @property
    def reply_credits(self) -> int:
        if self.reply_mailbox is None:
            return 0
        return self.reply_mailbox.n_slots - (self.reply_tail
                                             - self.reply_mailbox.consumed)

    def oldest_inflight_age(self, now: float | None = None) -> float:
        """Age (seconds) of the oldest tracked frame still awaiting its
        target's sweep — the liveness floor signal.  0.0 when nothing is
        in flight.  Covers handle sends on host lanes and corr-carrying
        stages on device lanes (``corr_by_coords``), so a wedged mesh is
        as visible as a wedged host ring."""
        now = time.monotonic() if now is None else now
        oldest = None
        for r in self.rings:
            for slot, rec in r.inflight.items():
                if slot < r.mailbox.consumed:
                    continue            # consumed by an external sweeper
                if oldest is None or rec.sent_at < oldest:
                    oldest = rec.sent_at
            for _, sent_at in r.corr_by_coords.values():
                if oldest is None or sent_at < oldest:
                    oldest = sent_at
            for rec in r.agg_by_coords.values():
                if oldest is None or rec.sent_at < oldest:
                    oldest = rec.sent_at
        return 0.0 if oldest is None else max(0.0, now - oldest)

    def summary(self) -> str:
        s = self.stats
        agg = (f" agg={s['agg_sent']}x{s['agg_subs'] / s['agg_sent']:.1f}"
               if s.get("agg_sent") else "")
        return (f"{self.name:<12s} fabric={self.fabric.kind:<9s} "
                f"sent={s['sent']:<4d} slim={s['slim_sent']:<4d} "
                f"delivered={s['delivered']:<4d} "
                f"rejected={s['rejected']:<3d} nacks={s['nacks']:<3d} "
                f"backpressure={s['backpressure']:<3d} "
                f"replies={s['replies']:<4d} "
                f"credits={self.credits}{agg}")


class Dispatcher:
    """One source fanning ifunc frames out to heterogeneous targets."""

    def __init__(self, src_ctx=None, engine: ProgressEngine | None = None, *,
                 coalesce: bool = False, obs: Obs | None = None):
        self.src_ctx = src_ctx
        self.engine = engine if engine is not None else ProgressEngine()
        self.peers: dict[str, Peer] = {}
        self._rr = 0             # fairness cursor over (peer, ring) lanes
        self.stats = {"sent": 0, "polled": 0, "poll_rounds": 0, "nacks": 0,
                      "replies": 0, "reply_dropped": 0, "agg_sent": 0,
                      "streams": 0, "timed_out": 0}
        # observability bundle: counters + flight recorder by default,
        # span tracing when the caller opted in (Obs(trace=True)).  One
        # bundle is shared across the dispatcher, its engine, and every
        # peer's target context, so cross-peer traces land in one file.
        self.obs = obs if obs is not None else Obs("dispatcher")
        self.obs.metrics.register_dict("dispatcher", self.stats)
        if getattr(self.engine, "obs", None) is None:
            self.engine.obs = self.obs
            self.obs.metrics.register_dict("engine", self.engine.stats)
        # task-runtime hooks (see repro.tasks): the router receives
        # (corr_id, name, value, is_err, decoded); the codec provides
        # encode(value)->bytes / encode_error(exc)->bytes for reply frames
        self.reply_router = None
        self.reply_codec = None
        self._coalesce = False
        self._agg_max_subs = 16
        self._agg_max_age = 5e-4
        self._agg_max_sub_bytes = 16 << 10
        self._streaming = False
        self._stream_chunk = 256 << 10
        self._stream_window = 4
        self._stream_threshold = None    # None -> _agg_max_sub_bytes
        self._stream_nonce = 0           # monotone: unique per stream open
        self._active_streams: list[_StreamTx] = []
        self._sweep_raise = None   # deferred mid-batch ifunc exception (a
        #       corr-less poisoned slot behind already-swept frames): poll
        #       re-raises it only after processing those frames' statuses
        self.faults = None       # FaultInjector: consulted (when set) at the
        #       poll loop (down peers stop being swept), the post point
        #       (k-th-put drops), and the ElasticController's beat pump
        self.pollers: list = []  # side-band callables invoked at every
        #       poll() entry — the ElasticController rides here so
        #       heartbeats pump/sweep on the same cadence as data traffic
        if coalesce:
            self.set_coalescing(True)

    def set_coalescing(self, enabled: bool = True, *, max_subs: int = 16,
                       max_age: float = 5e-4,
                       max_sub_bytes: int = 16 << 10) -> None:
        """Turn coalesced dispatch on/off.  ``max_subs`` caps sub-records
        per aggregate (an enqueue reaching it flushes immediately, so a
        steady burst ships in full containers); ``max_age`` (seconds)
        bounds how long the oldest queued record may wait before a poll
        force-flushes its queue — the adaptive knob that keeps a trickle
        workload's latency within one poll of the singleton path.
        ``max_sub_bytes`` bounds the payload size worth aggregating:
        coalescing amortizes *per-message* protocol overhead, and past a
        few KiB the wire is bandwidth-bound — bigger records bypass the
        queue and ship as plain SLIM singletons (after flushing anything
        queued ahead of them, so FIFO holds)."""
        if max_subs < 1:
            raise TransportError(f"max_subs must be >= 1, got {max_subs}")
        self._coalesce = enabled
        self._agg_max_subs = max_subs
        self._agg_max_age = max_age
        self._agg_max_sub_bytes = max_sub_bytes

    def set_streaming(self, enabled: bool = True, *,
                      chunk_bytes: int = 256 << 10, window: int = 4,
                      threshold: int | None = None) -> None:
        """Turn streamed large-payload dispatch on/off.  ``chunk_bytes`` is
        the per-chunk put size (clamped per lane so ``window`` cells plus
        the FULL-fallback prefix fit one ring slot), ``window`` the
        pipelining depth (chunks in flight past the target's consume
        cursor), ``threshold`` the payload size above which
        ``send_ifunc``/coalesced sends auto-route into the stream path
        (None: the coalescing bypass bound ``max_sub_bytes``, so the
        store-and-forward singleton cliff disappears exactly where the
        bypass used to ship it)."""
        if chunk_bytes < 1:
            raise TransportError(f"chunk_bytes must be >= 1, got {chunk_bytes}")
        if window < 1:
            raise TransportError(f"window must be >= 1, got {window}")
        self._streaming = enabled
        self._stream_chunk = chunk_bytes
        self._stream_window = window
        self._stream_threshold = threshold

    @property
    def _stream_thr(self) -> int:
        t = self._stream_threshold
        return self._agg_max_sub_bytes if t is None else t

    # -- topology -----------------------------------------------------------

    def add_peer(self, name: str, fabric: Fabric, target_ctx, *,
                 n_slots: int = DEFAULT_N_SLOTS,
                 slot_size: int = DEFAULT_SLOT_SIZE,
                 rings: int = 1, stripe: bool = False,
                 target_args: dict | None = None,
                 codec=None, **mailbox_kw) -> Peer:
        """``mailbox_kw`` passes backend-specific binds through to
        ``fabric.open_mailbox`` (e.g. ``prog=``/``externals=`` on the
        device-mesh fabric).  ``stripe=True`` (with ``rings > 1``) stripes
        the peer's traffic round-robin across its rings under one demux:
        sends rotate strictly (blocking on the rotation ring's credits
        rather than skipping ahead) and the poll consumes in the same
        rotation, so per-peer FIFO holds while a hot peer's slot budget
        scales with M rings.  Striped peers accept ``ring=None`` sends
        only — an explicit ring index would punch holes in the rotation.
        ``codec`` (id, name, or Codec) negotiates the wire codec streamed
        sends to this peer encode their chunks with (frame v2.5)."""
        if name in self.peers:
            raise TransportError(f"peer {name!r} already attached")
        peer = Peer(name, fabric, target_ctx,
                    target_args if target_args is not None else {})
        if codec is not None:
            c = WC.get_codec(codec)
            peer.codec = None if c.id == WC.RAW else c
        for _ in range(rings):
            mb = fabric.open_mailbox(target_ctx, n_slots, slot_size,
                                     **mailbox_kw)
            ch = fabric.connect(self.src_ctx, mb)
            if hasattr(mb, "obs"):
                # a lane with no target context (the device lane) traces
                # its own layers into this bundle
                mb.obs = self.obs
            peer.rings.append(RingState(mb, ch))
        peer.stripe = stripe and rings > 1
        self.peers[name] = peer
        self.obs.metrics.register_dict(f"peer.{name}", peer.stats)
        if (target_ctx is not None
                and getattr(target_ctx, "obs", None) is None
                and hasattr(target_ctx, "obs")):
            # share the bundle with the target side: execute/sweep spans
            # land in the same trace as the source's put spans
            target_ctx.obs = self.obs
        return peer

    def set_peer_codec(self, name: str, codec) -> None:
        """(Re-)negotiate the wire codec streamed sends to ``name`` encode
        their chunks with — the runtime half of codec negotiation: the
        decode side *advertises* accepted codecs in its admission ack and
        the sender arms the winner here, instead of baking one in at
        ``add_peer`` time.  Safe while streams are idle; an in-flight
        stream keeps the codec it opened with (``_StreamTx`` snapshots
        it), so a renegotiation never splits one payload across codecs."""
        peer = self.peers[name]
        c = WC.get_codec(codec)
        peer.codec = None if c.id == WC.RAW else c

    def attach_reply_ring(self, name: str, mailbox, channel) -> None:
        """Give a host peer a result-return path: ``mailbox`` is a
        source-owned ring (opened on the *source* context), ``channel`` the
        target->source path into it.  Corr-carrying requests executed at
        this peer post their outputs here as FLAG_REPLY frames; device-mesh
        peers need none (sweep results are correlated directly)."""
        peer = self.peers[name]
        if peer.fabric.kind == "device":
            raise TransportError(
                "device-mesh peers reply through the sweep, not a ring")
        peer.reply_mailbox = mailbox
        peer.reply_channel = channel
        peer.reply_tail = 0

    def remove_peer(self, name: str) -> None:
        """Cleanly retire a peer: release its slab-backed channels, drop
        queued coalesced sub-records and NACK retransmits, clear stripe
        rotation and in-flight tracking, and unregister its obs alias (so a
        re-admitted peer's stats dict reclaims ``peer.<name>`` instead of
        landing under a uniquified suffix).  Idempotent — recovery paths
        (controller deadline, explicit teardown, tests) may race to call it.
        Does NOT resolve in-flight futures; call :meth:`fail_inflight`
        (scoped via ``peers={name}``) *before* removal if the peer died
        with work outstanding."""
        peer = self.peers.pop(name, None)
        if peer is None:
            return
        for r in peer.rings:
            self.engine.release_slab(r.channel)
            r.inflight.clear()
            r.corr_by_coords.clear()
            r.agg_by_coords.clear()
        if peer.reply_channel is not None:
            self.engine.release_slab(peer.reply_channel)
        peer.resend.clear()
        for q in peer.coalesce.values():
            q.subs.clear()
        peer.coalesce.clear()
        peer.stripe_tx = peer.stripe_rx = 0
        self._active_streams = [tx for tx in self._active_streams
                                if tx.peer is not peer]
        self.obs.metrics.unregister_dict(f"peer.{name}", peer.stats)
        self._rr = 0             # lane list shrank: restart the fair cursor

    # -- source side --------------------------------------------------------

    def _slim_ok(self, peer: Peer, lib) -> bool:
        """SLIM-eligible: device lanes link at mailbox-open time (code never
        travels); host lanes need a confirmed FULL delivery of this digest."""
        if peer.fabric.kind == "device":
            return True
        return lib.code_digest in peer.cached

    def _check_full_fits(self, lane: RingState, lib, payload_len: int,
                         cont_len: int = 0) -> None:
        """A SLIM frame must stay FULL-retransmittable: if the target evicts
        the digest, the NACK fallback rebuilds code + payload (+ any
        continuation descriptor) into this same ring — reject at send time
        rather than crash a later drain."""
        need = (F.HEADER_LEN + len(lib.code) + payload_len + cont_len
                + F.TRAILER_LEN)
        if need > lane.mailbox.slot_size:
            raise TransportError(
                f"SLIM frame's FULL fallback ({need}B) exceeds slot "
                f"{lane.mailbox.slot_size}B — NACK retransmit impossible")

    def _agg_eligible(self, peer: Peer) -> bool:
        """Aggregate-container eligible: host lanes always; device lanes
        only when their mailboxes were opened agg-bound (``agg_k=`` — the
        put transcodes a container into a K-sub word-frame and the sweep
        executes all K per ring visit)."""
        if peer.fabric.kind != "device":
            return True
        return all(getattr(r.mailbox, "supports_agg", False)
                   for r in peer.rings)

    def _pick_lane(self, peer: Peer, ring: int | None) -> RingState | None:
        if peer.stripe and ring is None:
            # strict rotation: block on the rotation ring's credits rather
            # than skip ahead — a skip would reorder the peer's frames
            lane = peer.rings[peer.stripe_tx % len(peer.rings)]
            return lane if lane.credits > 0 else None
        lanes = peer.rings if ring is None else [peer.rings[ring]]
        lane = max(lanes, key=lambda r: r.credits)
        return lane if lane.credits > 0 else None

    def _bp(self, peer: Peer) -> None:
        """Count (and flight-record) one backpressure event."""
        peer.stats["backpressure"] += 1
        if self.obs.enabled:
            self.obs.recorder.add("backpressure", peer.name,
                                  f"credits={peer.credits}")

    @staticmethod
    def _check_ring_kw(peer: Peer, ring: int | None) -> None:
        if ring is not None and peer.stripe:
            raise TransportError(
                f"striped peer {peer.name!r} accepts ring=None sends only "
                "(an explicit ring would punch a hole in the rotation)")

    def _post_view(self, peer: Peer, lane: RingState, view, rec, on_complete,
                   future=None):
        o = self.obs
        if o.enabled and rec is not None:
            o.recorder.add("put", peer.name,
                           f"{rec.name} corr={rec.corr_id} {len(view)}B"
                           f"{' slim' if rec.slim else ''}")
            if (o.tracer.enabled and rec.span is None
                    and peer.fabric.kind != "device"):
                # the wire span: post -> delivery confirmation (poll OK),
                # NACK, or reject — ended where the inflight record pops
                rec.span = o.tracer.begin(
                    f"put:{rec.name}@{peer.name}", cat="wire",
                    actor=getattr(self.src_ctx, "name", "source"),
                    corr=rec.corr_id or None, bytes=len(view))
        if (self.faults is not None
                and self.faults.should_drop_put(peer.name)):
            # injected wire loss: the source's bookkeeping proceeds exactly
            # as if the put landed (tx record, tail advance, stripe
            # rotation, stats) but the bytes never reach the target — the
            # frame is recovered only when the liveness deadline fires
            # fail_inflight, same as a genuinely lost put
            peer.stats["dropped_puts"] += 1
            if o.enabled:
                o.recorder.add("drop_put", peer.name,
                               f"{rec.name if rec else '?'} slot={lane.tail}")
        else:
            self.engine.post(lane.channel, view, lane.tail, peer=peer.name,
                             on_complete=on_complete, future=future)
        if rec is not None and peer.fabric.kind != "device":
            lane.inflight[lane.tail] = rec
            if len(lane.inflight) > 2 * lane.mailbox.n_slots:
                # target sweeps outside our poll loop (e.g. WorkerAgent):
                # drop records for slots already consumed elsewhere
                low = lane.mailbox.consumed
                for s in [s for s in lane.inflight if s < low]:
                    del lane.inflight[s]
        if (rec is not None and rec.corr_id
                and peer.fabric.kind == "device"):
            # device replies come back as sweep results at the coordinates
            # this send stages into (the Mailbox.slot_coords contract)
            lane.corr_by_coords[lane.mailbox.slot_coords(lane.tail)] = (
                rec.corr_id, rec.sent_at)
        if (rec is not None and rec.subs is not None
                and peer.fabric.kind == "device"):
            # device aggregates complete by coordinates: the sweep leaves
            # per-sub outcomes in Mailbox.last_agg keyed the same way
            lane.agg_by_coords[lane.mailbox.slot_coords(lane.tail)] = rec
        lane.tail += 1
        if peer.stripe and lane is peer.rings[
                peer.stripe_tx % len(peer.rings)]:
            peer.stripe_tx += 1          # rotation advances at the ONE post
            #                              point, so every path (singleton,
            #                              aggregate, resend) rotates
        peer.stats["sent"] += 1
        peer.stats["bytes"] += len(view)
        if rec is not None and rec.slim:
            peer.stats["slim_sent"] += 1
        self.stats["sent"] += 1

    def _slab_post(self, peer: Peer, lane: RingState, frame, rec,
                   on_complete=None, future=None) -> None:
        """Stage a ready frame into the lane's slab cell and post it."""
        slab = self.engine.slab_slot(lane.channel, lane.tail)
        n = len(frame)
        if n > len(slab):
            raise TransportError(
                f"frame {n}B exceeds slot {lane.mailbox.slot_size}B")
        slab[:n] = frame
        self._post_view(peer, lane, slab[:n], rec, on_complete, future)

    def _flush_resends(self, peer: Peer) -> bool:
        """Post queued FULL retransmits (NACK fallback) ahead of any new
        traffic; False while the queue cannot drain.

        Retransmits are held until the peer's rings are quiescent (every
        in-flight frame resolved): an eviction NACKs *all* in-flight SLIM
        frames of the digest, but the NACKs surface one poll at a time —
        posting the first rebuild (or any newer frame) before the rest have
        reported would reorder execution at the target.  Waiting for
        quiescence makes the resend queue a faithful replay of ring order,
        so per-peer FIFO survives eviction storms."""
        if not peer.resend:
            return True
        if any(r.tail != r.mailbox.consumed for r in peer.rings):
            return False                       # storm not fully observed yet
        while peer.resend:
            lane = self._pick_lane(peer, None)
            if lane is None:
                return False
            msg = peer.resend.popleft()
            o = self.obs
            if isinstance(msg, _StreamResend):
                # NACKed SLIM stream: re-open FULL from chunk 0 under a
                # fresh nonce (the miss surfaced at the descriptor, before
                # any chunk was consumed — nothing replays; the nonce keeps
                # the dead open's still-racing chunk puts unmistakable)
                tx = msg.tx
                tx.desc = replace(tx.desc, nonce=self._next_nonce())
                if o.enabled:
                    o.recorder.add("resend", peer.name,
                                   f"stream {tx.handle.lib.name} "
                                   f"corr={tx.corr_id} FULL re-open")
                self._open_stream(peer, lane, tx, slim=False)
                peer.stats["resent"] += 1
                continue
            rec = _TxRec(msg.handle.lib.name, msg.handle.lib.code_digest,
                         msg.handle, slim=False,
                         corr_id=getattr(msg, "corr_id", 0))
            if o.enabled:
                o.recorder.add("resend", peer.name,
                               f"{rec.name} corr={rec.corr_id} FULL")
                if o.tracer.enabled:
                    # the retransmit is a child of the frame's logical
                    # lifetime: same corr as the NACKed wire span, its own
                    # interval under the "resend" category
                    rec.span = o.tracer.begin(
                        f"resend:{rec.name}@{peer.name}", cat="resend",
                        actor=getattr(self.src_ctx, "name", "source"),
                        corr=rec.corr_id or None)
            self._slab_post(peer, lane, msg.frame, rec)
            peer.stats["resent"] += 1
        return True

    # -- streamed large payloads (frame v2.5) --------------------------------

    def _next_nonce(self) -> int:
        self._stream_nonce += 1
        return self._stream_nonce & 0xFFFFFFFF

    def _stream_geometry(self, peer: Peer, lane: RingState, lib,
                         total: int, chunk: int, window: int) -> F.StreamDesc:
        """Commit a stream's chunk geometry, clamped so the frame — sized
        for its FULL fallback (header + code + descriptor + cells +
        trailer) — fits one ring slot even after a NACK rebuild restores
        the code section."""
        avail = (lane.mailbox.slot_size - F.HEADER_LEN - len(lib.code)
                 - F.STREAM_DESC_LEN - F.TRAILER_LEN)
        max_chunk = avail - F.CHUNK_OVERHEAD
        if max_chunk < 1:
            raise TransportError(
                f"slot {lane.mailbox.slot_size}B too small for even one "
                f"stream chunk cell past the {len(lib.code)}B code section")
        chunk = max(1, min(chunk, total, max_chunk))
        n_chunks = -(-total // chunk)
        window = max(1, min(window, n_chunks))
        while window > 1 and window * (chunk + F.CHUNK_OVERHEAD) > avail:
            window -= 1
        sflags = F.SFLAG_EXEC_ON_ARRIVAL if lib.streaming else 0
        codec_id = WC.RAW if peer.codec is None else peer.codec.id
        return F.StreamDesc(total, n_chunks, chunk, window, codec_id,
                            sflags, chunk + F.CHUNK_OVERHEAD,
                            self._next_nonce())

    @staticmethod
    def _encode_chunk(tx: _StreamTx, seq: int):
        """Codec-negotiated wire form of chunk ``seq``: (hdr, data, seal)
        where ``data`` is the codec output, or a zero-copy view into the
        payload when the codec is absent / doesn't shrink this chunk."""
        desc = tx.desc
        off = seq * desc.chunk_bytes
        raw = tx.payload[off:off + desc.chunk_bytes]
        # chunk 0 ships bit-exact under a lossy codec: the payload prefix
        # carries routing fields arrival-executing ifuncs peek at
        skip = tx.codec is None or (seq == 0 and tx.codec.lossy)
        coded = None if skip else tx.codec.encode(raw)
        if coded is None:
            data, used = raw, WC.RAW
        else:
            data, used = coded, tx.codec.id
        hdr, seal = F.pack_chunk_hdr(seq, len(data), len(raw), used,
                                     nonce=desc.nonce)
        return hdr, data, seal

    def _open_stream(self, peer: Peer, lane: RingState, tx: _StreamTx, *,
                     slim: bool) -> None:
        """Post a stream's open.  When every chunk fits the frame's cell
        window (``n_chunks <= window``), the whole frame — prefix, cells,
        trailer — goes out as ONE scatter-gather put (eager open; chunk
        data segments stay zero-copy views into the payload) and the
        stream never enters the chunk pump.  Otherwise: header + code +
        descriptor as one prefix put, the frame trailer withheld (the
        descriptor barrier), the ``window x cell`` gap never written, and
        the pump pipelines the chunks.  Either way the slot's
        :class:`_TxRec` carries the completion."""
        lib = tx.handle.lib
        code = b"" if slim else lib.code
        desc = tx.desc
        plen = F.stream_payload_len(desc.window, desc.cell)
        slab = self.engine.slab_slot(lane.channel, lane.tail)
        flen = F.seal_frame(slab, lib.name, code, lib.kind, plen,
                            digest=lib.code_digest, slim=slim,
                            corr_id=tx.corr_id, flags=F.FLAG_STREAM)
        F.pack_stream_desc(slab, F.HEADER_LEN + len(code), desc)
        prefix = F.HEADER_LEN + len(code) + F.STREAM_DESC_LEN
        tx.peer = peer
        tx.lane = lane
        tx.abs_slot = lane.tail
        tx.cells_base = prefix
        tx.next_send = 0
        tx.dead = False
        eager = desc.n_chunks <= desc.window
        if eager:
            # Eager open: chunk headers and seals stage INTO the slab at
            # their frame offsets, so every glue run (prefix|hdr,
            # seal|next-hdr, ...) that is byte-contiguous in the frame
            # collapses to one slab-view segment — for an uncompressed
            # stream the whole frame is [glue][data][glue][data]...[glue]
            # and the putv carries 2n+1 segments, the data ones zero-copy
            # views into the caller's payload.
            segs = []
            run_s, run_e = 0, prefix
            wire = prefix
            codec, nonce, chunk = tx.codec, desc.nonce, desc.chunk_bytes
            for seq in range(desc.n_chunks):
                cell = prefix + desc.cell_off(seq)
                raw = tx.payload[seq * chunk:(seq + 1) * chunk]
                skip = codec is None or (seq == 0 and codec.lossy)
                coded = None if skip else codec.encode(raw)
                if coded is None:
                    data, used = raw, WC.RAW
                else:
                    data, used = coded, codec.id
                nd = len(data)
                if cell != run_e:            # codec gap: run breaks here
                    segs.append((run_s, slab[run_s:run_e]))
                    run_s = cell
                run_e = cell + F.CHUNK_HDR_LEN
                F.pack_chunk_into(slab, cell, run_e + nd, seq, nd,
                                  len(raw), used, nonce=nonce)
                segs.append((run_s, slab[run_s:run_e]))
                segs.append((run_e, data))
                run_s = run_e + nd
                run_e = run_s + F.CHUNK_SEAL_LEN
                wire += F.CHUNK_OVERHEAD + nd
            segs.append((run_s, slab[run_s:run_e]))
            self.engine.post_stream_frame(lane.channel, lane.tail, segs,
                                          flen, peer=peer.name,
                                          future=tx.future)
            tx.next_send = desc.n_chunks
            peer.stats["bytes"] += wire + F.TRAILER_LEN
            peer.stats["stream_chunks"] += desc.n_chunks
        else:
            self.engine.post_stream_open(lane.channel, slab[:prefix], flen,
                                         lane.tail, peer=peer.name,
                                         future=tx.future)
            peer.stats["bytes"] += prefix + F.TRAILER_LEN
        rec = _TxRec(lib.name, lib.code_digest, tx.handle, slim,
                     corr_id=tx.corr_id, stream=tx)
        o = self.obs
        if o.enabled:
            o.recorder.add("stream_open", peer.name,
                           f"{lib.name} corr={tx.corr_id} "
                           f"{desc.total_len}B/{desc.n_chunks}ch"
                           f"{' eager' if eager else ''}"
                           f"{' slim' if slim else ''}")
            if o.tracer.enabled:
                rec.span = o.tracer.begin(
                    f"stream:{lib.name}@{peer.name}", cat="stream",
                    actor=getattr(self.src_ctx, "name", "source"),
                    corr=tx.corr_id or None, bytes=desc.total_len,
                    chunks=desc.n_chunks)
        lane.inflight[lane.tail] = rec
        lane.tail += 1
        peer.stats["sent"] += 1
        if slim:
            peer.stats["slim_sent"] += 1
        self.stats["sent"] += 1
        if eager:
            self.engine.flush(lane.channel)
        elif tx not in self._active_streams:
            self._active_streams.append(tx)

    def send_stream(self, peer_name: str, handle, payload, *,
                    ring: int | None = None, corr_id: int = 0, future=None,
                    chunk_bytes: int | None = None,
                    window: int | None = None) -> bool:
        """Stream one large payload to a host peer: ONE ring slot, ONE
        credit, the payload delivered as pipelined per-chunk puts instead
        of a store-and-forward frame bounded by the slot size.  The
        payload view must stay stable (unmutated) until the stream
        resolves — chunks are posted zero-copy straight from it.  SLIM
        framing, NACK FULL-rebuild, corr_id replies, and liveness
        (``fail_inflight``) work exactly as for singleton frames.
        Returns False on backpressure like any send."""
        peer = self.peers[peer_name]
        if peer.fabric.kind == "device":
            raise TransportError(
                "streams are host-tier only (the device mesh has no "
                "sub-slot addressing)")
        if peer.stripe:
            raise TransportError(
                f"striped peer {peer.name!r} cannot stream: a slot held "
                "across sweeps would wedge the strict consume rotation")
        pv = payload if isinstance(payload, memoryview) \
            else memoryview(payload)
        if pv.ndim != 1 or pv.itemsize != 1:
            pv = pv.cast("B")
        total = len(pv)
        if total == 0:
            raise TransportError("cannot stream an empty payload")
        if not self._flush_resends(peer):
            self._bp(peer)
            return False
        if not self._flush_coalesce_peer(peer):
            self._bp(peer)                    # FIFO: queued records go first
            return False
        lane = self._pick_lane(peer, ring)
        if lane is None:
            self._bp(peer)
            return False
        lib = handle.lib
        desc = self._stream_geometry(
            peer, lane, lib, total,
            self._stream_chunk if chunk_bytes is None else chunk_bytes,
            self._stream_window if window is None else window)
        tx = _StreamTx(handle, pv, desc, peer.codec, peer, lane, lane.tail,
                       0, corr_id=corr_id, future=future)
        self._open_stream(peer, lane, tx, slim=self._slim_ok(peer, lib))
        peer.stats["streams"] += 1
        self.stats["streams"] += 1
        self._pump_streams()
        return True

    def _pump_streams(self) -> int:
        """Advance every active stream: post chunks (codec-encoded when
        the negotiated codec shrinks them, raw otherwise) while the
        window is open — at most ``window`` chunks past the target's
        consume cursor — then flush the touched channels so the seals
        publish.  Fully-posted streams leave the pump; their slot's
        _TxRec carries the completion."""
        if not self._active_streams:
            return 0
        posted = 0
        flushes: dict[int, object] = {}
        still: list[_StreamTx] = []
        for tx in self._active_streams:
            if tx.dead:
                continue
            desc = tx.desc
            mb = tx.lane.mailbox
            coords = mb.slot_coords(tx.abs_slot)
            peer, channel = tx.peer, tx.lane.channel
            before = tx.next_send
            while tx.next_send < desc.n_chunks:
                if tx.next_send - mb.stream_consumed(coords) >= desc.window:
                    break                # window closed: cell still in use
                seq = tx.next_send
                hdr, data, seal = self._encode_chunk(tx, seq)
                self.engine.post_chunk(
                    channel, tx.abs_slot, tx.cells_base + desc.cell_off(seq),
                    hdr, data, seal, peer=peer.name)
                tx.next_send += 1
                posted += 1
                peer.stats["bytes"] += len(hdr) + len(data) + len(seal)
                peer.stats["stream_chunks"] += 1
            if tx.next_send > before:
                flushes[id(channel)] = channel
            if tx.next_send < desc.n_chunks:
                still.append(tx)
        self._active_streams = still
        for ch in flushes.values():
            self.engine.flush(ch)
        return posted

    # -- coalesced dispatch (frame v2.3 aggregates) --------------------------

    @staticmethod
    def _materialize_payload(lib, source_args, source_args_size) -> bytes:
        """Run the library's payload codec into a scratch buffer.  A
        coalesced record cannot write straight into a slab cell (its final
        offset inside the aggregate is unknown until flush), so small
        payloads pay one copy here — the per-frame header/signal/trailer
        amortization is worth orders of magnitude more at the sizes
        coalescing targets."""
        if source_args_size is None:
            try:
                source_args_size = len(source_args)
            except TypeError:
                source_args_size = 0
        max_size = int(lib.payload_get_max_size(source_args, source_args_size))
        buf = bytearray(max_size)
        used = lib.payload_init(memoryview(buf), max_size, source_args,
                                source_args_size)
        used = max_size if used in (None, 0) else int(used)
        return bytes(memoryview(buf)[:used])

    def _enqueue_sub(self, peer: Peer, handle, source_args, source_args_size,
                     ring, corr_id, future, cont) -> bool:
        """Queue one cache-warm invocation for aggregate packing (no ring
        credit is claimed until flush); flushes the queue first when this
        record would overflow the slot byte budget, and after adding when
        the sub-record cap fills.  The queue is bounded at a full ring's
        worth of containers (``max_subs * n_slots`` records): past that,
        with flushes backpressured, the send reports False like any
        credit-starved send — a producer outrunning its consumer is
        throttled, not buffered without bound."""
        lib = handle.lib
        lane0 = peer.rings[ring if ring is not None else 0]
        q0 = peer.coalesce.get(ring)
        if (q0 is not None and len(q0.subs)
                >= self._agg_max_subs * lane0.mailbox.n_slots):
            self._flush_coalesce_peer(peer, ring)
            q0 = peer.coalesce.get(ring)
            if (q0 is not None and len(q0.subs)
                    >= self._agg_max_subs * lane0.mailbox.n_slots):
                self._bp(peer)
                return False
        payload = self._materialize_payload(lib, source_args,
                                            source_args_size)
        if peer.fabric.kind != "device":
            # the NACK fallback rebuilds this record as a FULL singleton
            # into the same ring — reject now rather than crash a later
            # drain (device lanes size their slots for the bound word-frame
            # plus code that never travels: the check does not apply)
            self._check_full_fits(lane0, lib, len(payload),
                                  0 if cont is None else len(cont))
        sub = _PendingSub(handle, lib.name, lib.kind, lib.code_digest,
                          payload, corr_id, cont, future, time.monotonic())
        if (self._streaming and len(payload) > self._stream_thr
                and cont is None and peer.fabric.kind != "device"
                and not peer.stripe):
            # oversized record with streaming on: the slot-bounded bypass
            # singleton is the 64KiB cliff — stream it instead (send_stream
            # flushes queued records first, so FIFO holds)
            return self.send_stream(peer.name, handle, payload, ring=ring,
                                    corr_id=corr_id, future=future)
        if len(payload) > self._agg_max_sub_bytes:
            # bandwidth-bound record: aggregation buys nothing — ship it
            # as a plain SLIM singleton, after anything queued before it
            if not self._flush_coalesce_peer(peer, ring):
                self._bp(peer)
                return False
            lane = self._pick_lane(peer, ring)
            if lane is None:
                self._bp(peer)
                return False
            self._post_agg(peer, lane, [sub])
            return True
        q = peer.coalesce.get(ring)
        if q is None:
            q = peer.coalesce[ring] = _CoalesceQ()
        cap = lane0.mailbox.slot_size
        if q.subs and q.would_take(sub) > cap:
            self._flush_coalesce_peer(peer, ring)      # slot budget filled
            q = peer.coalesce.get(ring)
            if q is None:
                q = peer.coalesce[ring] = _CoalesceQ()
        q.add(sub)
        peer.stats["coalesced"] += 1
        if len(q.subs) >= self._agg_max_subs or q.bytes > cap:
            self._flush_coalesce_peer(peer, ring)      # cap (or lone record
            #                    too big to share a container): best-effort
            #                    flush now; on backpressure it stays queued
        return True

    def send_ifunc_many(self, peer_name: str, handle, payloads, *,
                        ring: int | None = None, corr_ids=None,
                        futures=None) -> int:
        """Bulk coalescing enqueue: K invocations of one handle in one
        call, with the payload codec, digest, and queue state hoisted out
        of the per-record loop — the per-call interpreter overhead that
        dominates a small-message burst is paid once per batch, not once
        per message.  ``corr_ids``/``futures`` (parallel lists) tie
        records to the task runtime's reply path.  Returns the number of
        records accepted, stopping early at a record it cannot accept —
        backpressure on a bypass record, or a record whose FULL fallback
        would not fit a ring slot (retrying the remainder through
        :meth:`send_ifunc` surfaces the hard error for that record).
        Falls back to per-record :meth:`send_ifunc` when coalescing is
        off or the peer is not aggregate-eligible."""
        peer = self.peers[peer_name]
        self._check_ring_kw(peer, ring)
        lib = handle.lib
        if not (self._coalesce and self._agg_eligible(peer)
                and self._slim_ok(peer, lib)):
            n = 0
            for i, args in enumerate(payloads):
                if not self.send_ifunc(
                        peer_name, handle, args, ring=ring,
                        corr_id=corr_ids[i] if corr_ids else 0,
                        future=futures[i] if futures else None):
                    break
                n += 1
            return n
        is_device = peer.fabric.kind == "device"
        lane0 = peer.rings[ring if ring is not None else 0]
        cap = lane0.mailbox.slot_size
        agg_k = getattr(lane0.mailbox, "agg_k", 0)
        full_base = F.HEADER_LEN + len(lib.code) + F.TRAILER_LEN
        gms, init = lib.payload_get_max_size, lib.payload_init
        name, kind, digest = lib.name, lib.kind, lib.code_digest
        max_subs = min(self._agg_max_subs, agg_k) if agg_k \
            else self._agg_max_subs
        max_sub_bytes = self._agg_max_sub_bytes
        now = time.monotonic()
        payloads = payloads if isinstance(payloads, (list, tuple)) \
            else list(payloads)
        N = len(payloads)
        n = i = 0
        q = peer.coalesce.get(ring)

        # -- direct slab pack: with nothing queued ahead (FIFO safe) and a
        # -- ring slot free, each record's payload codec writes STRAIGHT
        # -- into the slab cell at its final aggregate offset (the v2.4
        # -- columnar layout streams payloads first; the fixed headers
        # -- settle as one table write at finish) — no scratch buffer, no
        # -- second copy, no per-record queue bookkeeping
        if (q is None or not q.subs) and self._flush_resends(peer):
            sub_fixed = F.AGG_SUB_OVERHEAD
            kind_int = int(kind)
            while i < N:
                # peek the head record BEFORE touching the slab: a
                # bypass-sized head ships as a SLIM singleton and must not
                # pay for a container prologue it will never use
                args = payloads[i]
                try:
                    sz = len(args)
                except TypeError:
                    sz = 0
                mx = int(gms(args, sz))
                if (self._streaming and not is_device and not peer.stripe
                        and mx > self._stream_thr):
                    break                # oversized head: the generic loop
                #                          routes it into the stream path
                if not is_device and full_base + mx > cap:
                    break                # FULL fallback cannot fit a ring
                #                          slot: the generic loop errors
                lane = self._pick_lane(peer, ring)
                if lane is None:
                    break                # no credits: queue the remainder
                slab = self.engine.slab_slot(lane.channel, lane.tail)
                view = F.frame_payload_view(
                    slab, 0, len(slab) - F.HEADER_LEN - F.TRAILER_LEN)
                if mx > max_sub_bytes:
                    # bandwidth-bound record: aggregation buys nothing, so
                    # it ships as a SLIM singleton packed straight into
                    # the slab — the codec writes in place and seal_frame
                    # wraps around it, no scratch materialization, no
                    # queue round-trip (the bypass-parity contract:
                    # records the policy declines to aggregate pay
                    # singleton cost, not singleton + coalescing-
                    # machinery cost)
                    cid = corr_ids[i] if corr_ids else 0
                    with (self.obs.tracer.scope("repro.dispatch.pack", n=1)
                          if is_device else NULL_SCOPE):
                        used = init(view[:mx], mx, args, sz)
                        used = mx if used in (None, 0) else int(used)
                        fl = F.seal_frame(slab, name, b"", kind, used,
                                          digest=digest, slim=True,
                                          corr_id=cid)
                    self._post_view(peer, lane, slab[:fl],
                                    _TxRec(name, digest, handle,
                                           slim=True, corr_id=cid),
                                    None,
                                    futures[i] if futures else None)
                    n += 1
                    i += 1
                    continue             # slot consumed: repick a lane
                with self.obs.tracer.scope("repro.dispatch.pack") as sc:
                    off = F.begin_agg(view, [name])
                    prologue_end = off
                    hdrs: list[tuple] = []
                    subs: list[_PendingSub] = []
                    hdr_add, sub_add = hdrs.append, subs.append
                    budget = len(view) - 4
                    n_subs = 0
                    stop = False
                    # the inner loop IS the per-message cost of a coalesced
                    # burst: the sub-header row is built inline (plain
                    # records: name_idx 0, no flags, no cont) and the payload
                    # view is sliced once when the codec fills its estimate
                    while i < N and n_subs < max_subs:
                        args = payloads[i]
                        try:
                            sz = len(args)
                        except TypeError:
                            sz = 0
                        mx = int(gms(args, sz))
                        if not is_device and full_base + mx > cap:
                            stop = True      # FULL fallback cannot fit a ring
                            break            # slot: the generic loop errors
                        if mx > max_sub_bytes:
                            break            # seal the container first; the
                            #                  outer peek re-sees this record
                        n_subs += 1
                        if off + mx + n_subs * sub_fixed > budget:
                            n_subs -= 1
                            break            # container full: seal + continue
                        pv = view[off:off + mx]
                        used = init(pv, mx, args, sz)
                        used = mx if used in (None, 0) else int(used)
                        cid = corr_ids[i] if corr_ids else 0
                        hdr_add((0, kind_int, 0, digest, cid, used, 0))
                        sub_add(_PendingSub(
                            handle, name, kind, digest,
                            pv if used == mx else view[off:off + used],
                            cid, None, futures[i] if futures else None, now))
                        off += used
                        i += 1
                    if subs:
                        plen = F.finish_agg(view, prologue_end, off, hdrs)
                        fl = F.seal_frame(slab, F.AGG_NAME, b"", kind, plen,
                                          digest=F.NO_DIGEST,
                                          flags=F.FLAG_AGG)
                        sc.set_metadata(n=len(subs))
                if not subs:
                    break
                futs = [s.future for s in subs if s.future is not None]
                self._post_view(peer, lane, slab[:fl],
                                _TxRec(F.AGG_NAME, F.NO_DIGEST, None,
                                       slim=True, subs=subs),
                                None, futs or None)
                peer.stats["agg_sent"] += 1
                peer.stats["agg_subs"] += len(subs)
                peer.stats["coalesced"] += len(subs)
                self.stats["agg_sent"] += 1
                n += len(subs)
                if stop:
                    break

        # -- generic path: per-record through _enqueue_sub (records behind
        # -- an existing queue, bypass-sized records, backpressure
        # -- leftovers) — ONE implementation of the queueing policy
        while i < N:
            try:
                ok = self._enqueue_sub(peer, handle, payloads[i], None,
                                       ring,
                                       corr_ids[i] if corr_ids else 0,
                                       futures[i] if futures else None,
                                       None)
            except TransportError:
                break   # un-retransmittable record: stop here — the caller
                #         retries it through send_ifunc, which raises the
                #         TransportError with this record's identity
            if not ok:
                break   # queue bound hit with flushes backpressured
            i += 1
            n += 1
        return n

    def _post_agg(self, peer: Peer, lane: RingState,
                  subs: list[_PendingSub]) -> None:
        """Pack queued sub-records into the lane's slab cell and post: one
        container, one credit.  A single queued record ships as a plain
        SLIM singleton — the aggregate wrapper is never latency overhead."""
        pack = (self.obs.tracer.scope("repro.dispatch.pack", n=len(subs))
                if peer.fabric.kind == "device" else NULL_SCOPE)
        if len(subs) == 1:
            sub = subs[0]
            lib = sub.handle.lib
            slab = self.engine.slab_slot(lane.channel, lane.tail)
            with pack:
                n = F.pack_frame_into(slab, lib.name, b"", sub.payload,
                                      lib.kind, digest=lib.code_digest,
                                      slim=True, corr_id=sub.corr_id,
                                      cont=sub.cont)
            self._post_view(peer, lane, slab[:n],
                            _TxRec(lib.name, lib.code_digest, sub.handle,
                                   slim=True, corr_id=sub.corr_id),
                            None, sub.future)
            return
        # _PendingSub speaks the AggSub attribute protocol: pack directly,
        # no intermediate wire object per record.  The container header
        # carries the records' code kind: the device put rejects non-UVM
        # frames at the header, before parsing the payload.
        slab = self.engine.slab_slot(lane.channel, lane.tail)
        with pack:
            n = F.seal_agg_frame(slab, subs, kind=subs[0].kind)
        futs = [s.future for s in subs if s.future is not None]
        rec = _TxRec(F.AGG_NAME, F.NO_DIGEST, None, slim=True,
                     subs=list(subs))
        o = self.obs
        if o.tracer.enabled and peer.fabric.kind != "device":
            # the container flush is its own span: the coalesced records'
            # submit spans (tasks layer) nest around it by corr
            rec.span = o.tracer.begin(
                f"agg:{len(subs)}@{peer.name}", cat="agg",
                actor=getattr(self.src_ctx, "name", "source"),
                subs=len(subs), bytes=n)
        self._post_view(peer, lane, slab[:n], rec, None, futs or None)
        peer.stats["agg_sent"] += 1
        peer.stats["agg_subs"] += len(subs)
        self.stats["agg_sent"] += 1

    @staticmethod
    def _split_budget(subs: list[_PendingSub], cap: int,
                      max_subs: int) -> int:
        """Longest prefix of ``subs`` that packs into ONE container within
        the slot byte budget and the record cap.  Always >= 1: a lone
        record posts as a SLIM singleton, whose fit ``_check_full_fits``
        guaranteed at enqueue."""
        names: set = set()
        total = _CoalesceQ.BASE
        n = 0
        for s in subs:
            extra = (F.AGG_SUB_OVERHEAD + len(s.payload)
                     + (0 if s.cont is None else len(s.cont)))
            if s.name not in names:
                extra += 1 + len(s.name)
            if n and (total + extra > cap or n >= max_subs):
                break
            total += extra
            names.add(s.name)
            n += 1
        return n

    def _flush_coalesce_peer(self, peer: Peer,
                             ring: int | None = "all") -> bool:
        """Drain a peer's coalescing queue(s) into aggregate posts,
        splitting into as many containers as the slot budget requires —
        the enqueue-side byte count is only a flush *trigger*; a queue
        that overgrew while a flush was backpressured still drains
        correctly, one slot-sized container at a time.  False when a
        queue could not fully drain (no ring credits) — its remaining
        records stay queued, in order, for the next attempt."""
        if not peer.coalesce:
            return True
        if not self._flush_resends(peer):
            return False     # NACK retransmits outrank queued new traffic
        keys = list(peer.coalesce) if ring == "all" else [ring]
        ok = True
        for key in keys:
            q = peer.coalesce.get(key)
            if q is None or not q.subs:
                peer.coalesce.pop(key, None)
                continue
            subs = q.subs
            mb0 = peer.rings[key if key is not None else 0].mailbox
            cap = mb0.slot_size
            agg_k = getattr(mb0, "agg_k", 0)
            max_subs = min(self._agg_max_subs, agg_k) if agg_k \
                else self._agg_max_subs
            posted = 0
            while posted < len(subs):
                lane = self._pick_lane(peer, key)
                if lane is None:
                    self._bp(peer)
                    ok = False
                    break
                take = self._split_budget(subs[posted:], cap, max_subs)
                self._post_agg(peer, lane, subs[posted:posted + take])
                posted += take
            if posted >= len(subs):
                peer.coalesce.pop(key, None)
            elif posted:
                nq = _CoalesceQ()          # keep the unposted tail queued
                for s in subs[posted:]:
                    nq.add(s)
                peer.coalesce[key] = nq
        return ok

    def flush_coalesced(self, peer_name: str | None = None,
                        ring: int | None = "all") -> bool:
        """Explicit coalescing-queue flush (all peers by default)."""
        if peer_name is not None:
            return self._flush_coalesce_peer(self.peers[peer_name], ring)
        ok = True
        for p in self.peers.values():
            ok = self._flush_coalesce_peer(p, ring) and ok
        return ok

    def _age_flush(self) -> None:
        """Flush any queue whose oldest record has waited past the age
        bound — the poll-side half of the adaptive policy."""
        now = time.monotonic()
        for p in self.peers.values():
            if not p.coalesce:
                continue
            for key in list(p.coalesce):
                q = p.coalesce.get(key)
                if (q is not None and q.subs
                        and now - q.subs[0].enq_at >= self._agg_max_age):
                    self._flush_coalesce_peer(p, key)

    def send(self, peer_name: str, msg, *, ring: int | None = None,
             on_complete=None, future=None) -> bool:
        """Post one ifunc message to a peer.  Returns False (and counts a
        backpressure event) when every eligible ring is out of credits.

        The frame is staged into the engine's slab cell for the chosen ring
        slot; if the peer is known to have this handle's code digest cached,
        the code section is elided on the fly (SLIM framing).  A corr_id
        already sealed into the message's header rides along — including
        across the on-the-fly SLIM repack."""
        peer = self.peers[peer_name]
        self._check_ring_kw(peer, ring)
        if not self._flush_resends(peer):
            self._bp(peer)
            return False
        if not self._flush_coalesce_peer(peer):
            # queued coalesced records precede this frame in program order:
            # they must post first or per-peer FIFO breaks
            self._bp(peer)
            return False
        lane = self._pick_lane(peer, ring)
        if lane is None:
            self._bp(peer)
            return False
        frame = msg.frame if hasattr(msg, "frame") else msg
        handle = getattr(msg, "handle", None)
        if handle is None:                       # raw frame: no slim protocol
            self._slab_post(peer, lane, frame, None, on_complete, future)
            return True
        lib = handle.lib
        corr_id = getattr(msg, "corr_id", 0)   # mirrored from the header at
        #                          msg-create time: no hot-path header parse
        cont = getattr(msg, "cont", None)   # mirrored at msg-create time
        if cont is not None and peer.fabric.kind == "device":
            raise TransportError(
                "continuation frames are host-tier only (the device sweep "
                "has no forwarding hook)")
        already_slim = bool(getattr(msg, "slim", False))
        want_slim = self._slim_ok(peer, lib)
        rec = _TxRec(lib.name, lib.code_digest, handle,
                     already_slim or want_slim, corr_id=corr_id)
        if rec.slim and peer.fabric.kind != "device":
            self._check_full_fits(lane, lib, len(msg.payload_view),
                                  0 if cont is None else len(cont))
        if want_slim and not already_slim:
            # elide the code section while staging — the slab cell is the
            # only buffer the SLIM frame ever occupies; the continuation
            # descriptor rides along untouched
            slab = self.engine.slab_slot(lane.channel, lane.tail)
            n = F.pack_frame_into(slab, lib.name, b"", msg.payload_view,
                                  lib.kind, digest=lib.code_digest, slim=True,
                                  corr_id=corr_id, cont=cont)
            self._post_view(peer, lane, slab[:n], rec, on_complete, future)
        else:
            self._slab_post(peer, lane, frame, rec, on_complete, future)
        return True

    def send_ifunc(self, peer_name: str, handle, source_args,
                   source_args_size: int | None = None, *,
                   ring: int | None = None, on_complete=None,
                   corr_id: int = 0, future=None,
                   cont: bytes | None = None) -> bool:
        """Fully zero-copy send: skips IfuncMsg materialization — the
        payload codec writes directly into the peer's slab cell and the
        header is sealed around it in place.  SLIM framing is applied
        automatically once the peer's cache is known-warm.  ``corr_id``
        nonzero requests a result-return reply (the Future path);
        ``cont`` appends a packed continuation descriptor (the flow
        layer's peer-to-peer forwarding path — host fabrics only)."""
        peer = self.peers[peer_name]
        self._check_ring_kw(peer, ring)
        if cont is not None and peer.fabric.kind == "device":
            raise TransportError(
                "continuation frames are host-tier only (the device sweep "
                "has no forwarding hook)")
        if (self._streaming and cont is None and on_complete is None
                and peer.fabric.kind != "device" and not peer.stripe):
            if source_args_size is None:
                try:
                    source_args_size = len(source_args)
                except TypeError:
                    source_args_size = 0
            if int(handle.lib.payload_get_max_size(
                    source_args, source_args_size)) > self._stream_thr:
                # oversized payload: the slot-bounded singleton is the
                # 64KiB cliff — materialize once and stream it instead
                payload = self._materialize_payload(handle.lib, source_args,
                                                    source_args_size)
                return self.send_stream(peer_name, handle, payload,
                                        ring=ring, corr_id=corr_id,
                                        future=future)
        if (self._coalesce and on_complete is None
                and self._agg_eligible(peer)
                and self._slim_ok(peer, handle.lib)):
            # cache-warm send with coalescing on: queue for aggregate
            # packing instead of claiming a ring slot per message (device
            # lanes participate when their mailboxes are agg-bound)
            return self._enqueue_sub(peer, handle, source_args,
                                     source_args_size, ring, corr_id,
                                     future, cont)
        if not self._flush_resends(peer):
            self._bp(peer)
            return False
        if not self._flush_coalesce_peer(peer):
            self._bp(peer)                    # FIFO: queued records go first
            return False
        lane = self._pick_lane(peer, ring)
        if lane is None:
            self._bp(peer)
            return False
        lib = handle.lib
        if source_args_size is None:
            try:
                source_args_size = len(source_args)
            except TypeError:
                source_args_size = 0
        max_size = int(lib.payload_get_max_size(source_args, source_args_size))
        cont_len = 0 if cont is None else len(cont)
        slim = self._slim_ok(peer, lib)
        is_device = peer.fabric.kind == "device"
        if slim and not is_device:
            self._check_full_fits(lane, lib, max_size, cont_len)
        code = b"" if slim else lib.code
        slab = self.engine.slab_slot(lane.channel, lane.tail)
        if (F.HEADER_LEN + len(code) + max_size + cont_len
                + F.TRAILER_LEN) > len(slab):
            raise TransportError(
                f"frame would exceed slot {lane.mailbox.slot_size}B")
        with (self.obs.tracer.scope("repro.dispatch.pack", n=1)
              if is_device else NULL_SCOPE):
            pv = F.frame_payload_view(slab, len(code), max_size)
            used = lib.payload_init(pv, max_size, source_args,
                                    source_args_size)
            used = max_size if used in (None, 0) else int(used)
            n = F.seal_frame(slab, lib.name, code, lib.kind, used,
                             digest=lib.code_digest, slim=slim,
                             corr_id=corr_id, cont=cont)
        self._post_view(peer, lane, slab[:n],
                        _TxRec(lib.name, lib.code_digest, handle, slim,
                               corr_id=corr_id),
                        on_complete, future)
        return True

    def broadcast(self, make_msg) -> int:
        """``make_msg(peer) -> msg`` for every peer; returns #accepted."""
        return sum(bool(self.send(p, make_msg(peer)))
                   for p, peer in self.peers.items())

    def flush(self) -> int:
        """Publish all in-flight puts (completes trailers -> frames become
        consumable at the targets).  Coalescing queues flush first — an
        explicit flush means 'everything handed to send is on the wire'."""
        for p in self.peers.values():
            self._flush_coalesce_peer(p)
        if self._active_streams:
            self._pump_streams()
        return self.engine.flush()

    # -- target side: fairness-aware poll loop ------------------------------

    def _lanes(self) -> list[tuple[Peer, RingState]]:
        return [(p, r) for p in self.peers.values() for r in p.rings]

    def _rebuild_full(self, lane: RingState, abs_slot: int, rec: _TxRec):
        """NACK fallback: the SLIM frame still sits in the source slab cell
        for its slot (the credit only just returned, nothing has overwritten
        it); hand it to ``ifunc_msg_to_full`` to restore the code section."""
        A = _api()

        view = self.engine.slab_slot(lane.channel, abs_slot)
        return A.ifunc_msg_to_full(A.IfuncMsg(rec.handle, view, slim=True))

    def _sweep_task(self, peer: Peer, lane: RingState,
                    max_slots: int = 1) -> list:
        """Sweep up to ``max_slots`` ready slots of a reply-enabled host
        lane: per slot, capture the request's corr_id before execution
        destroys the frame, capture the ifunc's output
        (``target_args["result"]``) — or the exception it raised — after,
        and post the encoded reply.  An ifunc exception consumes the slot
        (clear + head advance) instead of wedging the ring; the error
        travels back as a FLAG_ERR reply.  A fire-and-forget frame
        (corr_id == 0) has no reply to carry the error, so after consuming
        the slot the exception re-raises to the poll caller — same
        visibility as a plain dispatcher; mid-batch, the raise is
        *deferred* until the statuses of the slots already swept in this
        batch have been processed (``poll`` re-raises it after this
        lane's completion), so a delivered aggregate ahead of a poisoned
        slot still confirms digests and resolves its futures.  Aggregate
        containers pass through untouched here (header corr is 0); their
        per-sub-record replies coalesce in :meth:`_complete_agg`."""
        Status = _api().Status

        mb = lane.mailbox
        out: list = []
        for _ in range(max_slots):
            buf = mb.slot_view(mb.head)
            hdr = mb.peek()                  # fabric-contract header peek
            corr = 0 if hdr is None else hdr.corr_id
            name = "" if hdr is None else hdr.name
            kind = F.CodeKind.PYBC if hdr is None else hdr.code_kind
            targs = peer.target_args
            if isinstance(targs, dict):
                targs.pop("result", None)
            err = None
            try:
                sts = mb.sweep(peer.target_ctx, targs, budget=1)
            except Exception as e:           # raised *inside* the ifunc
                err = e
                F.scrub_slot(buf)
                mb.streams.pop(mb.slot_coords(mb.head), None)   # a raising
                #              exec-on-arrival stream dies with its slot
                mb.head += 1                 # consume the poisoned slot
                mb.consumed += 1
                peer.stats["errors"] += 1
                if not corr:
                    if not out:
                        raise                # no future to carry the error
                    self._sweep_raise = e    # don't discard what the batch
                    break                    # already swept: raise after
                sts = [Status.OK]            # delivered — it just raised
            if corr and sts and sts[0] in (Status.OK, Status.REJECTED):
                if err is not None:
                    value, is_err = err, True
                elif sts[0] == Status.REJECTED:
                    value, is_err = TransportError(
                        str(peer.target_ctx.stats.get(
                            "last_reject", "frame rejected"))), True
                else:
                    value = (targs.get("result")
                             if isinstance(targs, dict) else None)
                    is_err = False
                self._post_reply(peer, name, kind, corr, value, is_err)
            out.extend(sts)
            if not sts or sts[-1] not in (Status.OK, Status.REJECTED,
                                          Status.NACK_UNCACHED):
                break                        # empty / in-progress: stop here
        return out

    def _complete_agg(self, peer: Peer, lane: RingState, rec: _TxRec,
                      coords) -> int:
        """Source-side completion of one delivered aggregate: walk the
        per-sub-record outcomes the target's sweep left in
        ``Mailbox.last_agg`` under ``coords`` — confirm cached digests,
        queue FULL-singleton retransmits for digest misses (ONLY the
        missed records; executed siblings are never replayed), and
        coalesce corr-carrying results into one reply frame (device
        lanes, which have no reply ring, route each result straight to
        the reply router instead).  Returns the number of consumed (OK or
        rejected) sub-records, i.e. this container's contribution to the
        poll budget."""
        A = _api()
        Status = A.Status
        o = self.obs
        if o.enabled:
            o.rtt_hist.observe((time.monotonic() - rec.sent_at) * 1e6)
            if rec.span is not None:
                o.tracer.end(rec.span, subs=len(rec.subs or ()))
                rec.span = None
        results = lane.mailbox.last_agg.pop(coords, None)
        if results is not None and len(results) != len(rec.subs):
            # a harvest that does not match the container we sent (an
            # external sweeper raced us, or the bounded stash evicted):
            # trusting per-index outcomes would misattribute NACKs —
            # treat as delivered-without-detail instead
            peer.stats["agg_harvest_lost"] += 1
            results = None
        cached_add = peer.cached.add
        subs = rec.subs
        ok_marker = A._AGG_PLAIN_OK
        if (results is not None and len(results) == len(subs)
                and all(r is ok_marker for r in results)):
            # the dominant outcome: every record executed clean,
            # fire-and-forget — the target handed back the shared OK
            # marker for all of them, so skip the per-record status
            # ladder (corr-carrying and device records always carry real
            # result objects and take the full walk below)
            for sub in subs:
                cached_add(sub.digest)
            peer.stats["delivered"] += len(subs)
            reply_subs = [(sub, None, False) for sub in subs if sub.corr_id]
            if reply_subs:
                self._post_agg_reply(peer, reply_subs)
            return len(subs)
        consumed = n_ok = n_rej = n_nack = n_err = 0
        reply_subs = []
        for i, sub in enumerate(rec.subs):
            res = (results[i] if results is not None and i < len(results)
                   else None)
            st = Status.OK if res is None else res.status
            if st == Status.NACK_UNCACHED:
                n_nack += 1
                if o.enabled:
                    o.recorder.add("nack", peer.name,
                                   f"agg sub {sub.name} corr={sub.corr_id}")
                peer.cached.discard(sub.digest)
                if sub.handle is not None:
                    lib = sub.handle.lib
                    frame = F.pack_frame(lib.name, lib.code, sub.payload,
                                         lib.kind, digest=lib.code_digest,
                                         corr_id=sub.corr_id, cont=sub.cont)
                    peer.resend.append(A.IfuncMsg(sub.handle, frame,
                                                  slim=False,
                                                  corr_id=sub.corr_id,
                                                  cont=sub.cont))
                else:
                    peer.stats["nack_lost"] += 1
                continue
            consumed += 1
            if st == Status.REJECTED:
                n_rej += 1
                if sub.corr_id:
                    err = (res.error if res is not None
                           and res.error is not None
                           else TransportError("sub-record rejected"))
                    reply_subs.append((sub, err, True))
                continue
            n_ok += 1
            cached_add(sub.digest)
            if sub.corr_id:
                if res is not None and res.error is not None:
                    n_err += 1
                    reply_subs.append((sub, res.error, True))
                else:
                    reply_subs.append(
                        (sub, res.value if res is not None else None, False))
        s = peer.stats                       # one batched stats update
        s["delivered"] += n_ok
        if n_rej:
            s["rejected"] += n_rej
        if n_err:
            s["errors"] += n_err
        if n_nack:
            s["nacks"] += n_nack
            self.stats["nacks"] += n_nack
        if reply_subs:
            if peer.fabric.kind == "device":
                # no reply ring on a mesh lane: the sweep's harvested
                # values ARE the results — route them directly, decoded
                for sub, value, is_err in reply_subs:
                    self._route_reply(sub.corr_id, peer.name, value,
                                      is_err, decoded=True)
                s["replies"] += len(reply_subs)
                self.stats["replies"] += len(reply_subs)
            else:
                self._post_agg_reply(peer, reply_subs)
        return consumed

    def _post_agg_reply(self, peer: Peer, reply_subs: list[tuple]) -> None:
        """Coalesce the results of one aggregate's corr-carrying records
        into ONE ``FLAG_AGG|FLAG_REPLY`` frame on the peer's reply ring —
        the response direction amortizes exactly like the request one.
        Falls back to singleton replies when there is only one result (or
        the encoded batch outgrows a reply slot)."""
        if peer.reply_channel is None or self.reply_codec is None:
            self.stats["reply_dropped"] += len(reply_subs)
            return
        codec = self.reply_codec
        wire = []
        for sub, value, is_err in reply_subs:
            try:
                payload = (codec.encode_error(value) if is_err
                           else codec.encode(value))
            except Exception as e:           # unencodable result: the error
                payload, is_err = codec.encode_error(e), True   # IS the reply
            wire.append(F.AggSub(sub.name, sub.kind, F.NO_DIGEST,
                                 sub.corr_id, payload, err=is_err))
        if (len(wire) > 1
                and F.agg_frame_len(wire) <= peer.reply_mailbox.slot_size):
            if peer.reply_credits <= 0:
                self._drain_replies(peer)
            slab = self.engine.slab_slot(peer.reply_channel, peer.reply_tail)
            n = F.seal_agg_frame(slab, wire, reply=True)
            self.engine.post(peer.reply_channel, slab[:n], peer.reply_tail,
                             peer=peer.name)
            peer.reply_tail += 1
            peer.stats["replies"] += len(wire)
            peer.stats["agg_replies"] += 1
            self.stats["replies"] += len(wire)
            return
        for sub, value, is_err in reply_subs:
            self._post_reply(peer, sub.name, sub.kind, sub.corr_id, value,
                             is_err)

    def _post_reply(self, peer: Peer, name: str, kind, corr: int, value,
                    is_err: bool) -> None:
        """Pack a result into a FLAG_REPLY frame and post it target->source.
        The source can always drain its own inbox, so a full reply ring is
        drained inline rather than dropping the result."""
        if peer.reply_channel is None or self.reply_codec is None:
            self.stats["reply_dropped"] += 1
            return
        if peer.reply_credits <= 0:
            self._drain_replies(peer)
        codec = self.reply_codec
        try:
            payload = (codec.encode_error(value) if is_err
                       else codec.encode(value))
        except Exception as e:               # unencodable result: the error
            payload, is_err = codec.encode_error(e), True   # IS the reply
        slab = self.engine.slab_slot(peer.reply_channel, peer.reply_tail)
        try:
            n = F.pack_reply_into(slab, name, payload, kind, corr, err=is_err)
        except F.FrameError as e:            # oversized value: error reply
            n = F.pack_reply_into(slab, name, codec.encode_error(e), kind,
                                  corr, err=True)
        self.engine.post(peer.reply_channel, slab[:n], peer.reply_tail,
                         peer=peer.name)
        peer.reply_tail += 1
        peer.stats["replies"] += 1
        self.stats["replies"] += 1

    def _route_reply(self, corr: int, name: str, value, is_err: bool,
                     decoded: bool) -> None:
        if self.reply_router is None:
            self.stats["reply_dropped"] += 1
            return
        self.reply_router(corr, name, value, is_err, decoded)

    def _drain_replies(self, peer: Peer, budget: int | None = None) -> int:
        """Source side of the reply path: flush the target's pending reply
        puts, then consume FLAG_REPLY frames from the peer's reply ring and
        hand them to the router.  Corrupt reply slots are cleared and
        counted, never wedged."""
        if peer.reply_mailbox is None:
            return 0
        self.engine.flush(peer.reply_channel)
        mb = peer.reply_mailbox
        n = 0
        while budget is None or n < budget:
            buf = mb.slot_view(mb.head)
            try:
                hdr = F.peek_header(buf)
            except F.FrameError:
                F.scrub_slot(buf)
                mb.head += 1
                mb.consumed += 1
                peer.stats["reply_rejects"] += 1
                continue
            if hdr is None or not F.trailer_arrived(buf, hdr):
                break
            if hdr.is_agg:
                # coalesced reply: one container, many corr_ids — one
                # vectorized table parse, one demux comprehension
                try:
                    routed = F.parse_agg(
                        F.frame_sections(buf, hdr)[1]).reply_tuples()
                except F.FrameError:
                    F.scrub_slot(buf)
                    mb.head += 1
                    mb.consumed += 1
                    peer.stats["reply_rejects"] = (
                        peer.stats.get("reply_rejects", 0) + 1)
                    continue
                F.clear_frame(buf, hdr)
                mb.head += 1
                mb.consumed += 1
                for corr, name, payload, is_err in routed:
                    if peer.fence and F.corr_gen(corr) < peer.fence:
                        peer.stats["fenced_orphans"] += 1
                        continue     # stale-generation record in a fresh
                        #              container: fence per record
                    self._route_reply(corr, name, payload, is_err,
                                      decoded=False)
                n += len(routed)
                continue
            payload = bytes(F.frame_sections(buf, hdr)[1])
            corr, name, is_err = hdr.corr_id, hdr.name, hdr.is_err
            F.clear_frame(buf, hdr)
            mb.head += 1
            mb.consumed += 1
            if peer.fence and F.corr_gen(corr) < peer.fence:
                # a reply stamped under an earlier fleet generation: this
                # peer died and was re-admitted since the request was
                # allocated, so whatever future the corr named was already
                # resolved (TransportError) by fail_inflight — executing the
                # route would resurrect it.  Count + drop.
                peer.stats["fenced_orphans"] += 1
                if self.obs.enabled:
                    self.obs.recorder.add(
                        "fenced_orphan", peer.name,
                        f"corr={corr} gen={F.corr_gen(corr)} "
                        f"fence={peer.fence}")
                n += 1
                continue
            self._route_reply(corr, name, payload, is_err, decoded=False)
            n += 1
        return n

    def poll_replies(self) -> int:
        """Drain every peer's reply ring; returns replies routed."""
        return sum(self._drain_replies(p) for p in self.peers.values())

    def poll(self, budget: int | None = None) -> int:
        """Drain up to ``budget`` messages total across all peers' rings,
        deficit-round-robin.  A *budgeted* poll visits every lane once per
        round, consuming at most one message per lane per round (so no
        ring monopolizes the poller), starting one lane past last round's
        first server.  An *unbudgeted* poll (the drain path) sweeps a
        whole ring's worth of ready slots per lane visit instead — one
        batched pass per lane, not one poll-loop round per message.  A
        device-mesh lane always sweeps whole-ring (its sweep is a single
        compiled pass); an aggregate container likewise yields all its
        sub-records at once — both can overshoot ``budget`` by one sweep.

        OK deliveries confirm the target's code cache for the frame's
        digest (enabling SLIM framing); NACK_UNCACHED consumes the slot,
        un-confirms the digest, and queues a FULL retransmit — for an
        aggregate, per sub-record.  Replies (result-return frames, device
        sweep results with corr-ids) are routed to the reply_router as a
        side effect; they do not count against ``budget``."""
        Status = _api().Status

        for cb in tuple(self.pollers):
            # side-band pollers (ElasticController heartbeat pump/sweep)
            # run BEFORE the lane snapshot: one may retire a dead peer,
            # and the data sweep below must not visit its rings
            cb()
        if self._coalesce:
            self._age_flush()            # adaptive bound: no record waits
            #                              longer than agg_max_age queued
        lanes = self._lanes()
        if not lanes:
            return 0
        done = 0
        self.stats["poll_rounds"] += 1
        take = 1 if budget is not None else None    # None -> whole ring
        progressed = True
        while progressed and (budget is None or done < budget):
            progressed = False
            if self._active_streams and self._pump_streams():
                progressed = True        # chunks posted: windows the sweeps
                #                          below just opened refill in-poll
            start = self._rr % len(lanes)
            for k in range(len(lanes)):
                peer, lane = lanes[(start + k) % len(lanes)]
                if budget is not None and done >= budget:
                    break
                if (self.faults is not None
                        and self.faults.is_down(
                            peer.name,
                            delivered=peer.stats["delivered"])):
                    continue     # injected death: the peer's progress side
                    #              is gone — posted frames sit undelivered
                    #              until the heartbeat deadline recovers them
                if peer.stripe and lane is not peer.rings[
                        peer.stripe_rx % len(peer.rings)]:
                    continue         # striped peer: consume in the same
                    #                  strict rotation the posts followed,
                    #                  one frame per visit — per-peer FIFO
                take_eff = 1 if peer.stripe else take
                track = peer.fabric.kind != "device"
                slot = lane.mailbox.head
                if track and peer.reply_channel is not None:
                    sts = self._sweep_task(
                        peer, lane,
                        take_eff if take_eff is not None
                        else lane.mailbox.n_slots)
                    coords = res_new = None
                elif track:
                    sts = lane.mailbox.sweep(peer.target_ctx,
                                             peer.target_args,
                                             budget=take_eff)
                    coords = res_new = None
                else:
                    res_before = len(getattr(lane.mailbox, "results", ()))
                    sts = lane.mailbox.sweep(peer.target_ctx,
                                             peer.target_args, budget=1)
                    coords = getattr(lane.mailbox, "last_coords", None)
                    res_new = list(getattr(lane.mailbox, "results",
                                           ())[res_before:])
                # the device lane's completions are a traced layer; a host
                # lane's per-message poll stays free of the scope's cost
                sc = (self.obs.tracer.scope("repro.dispatch.complete")
                      if sts and not track else None)
                if sc is not None:
                    sc.__enter__()
                done0 = done
                try:
                    ri = 0                       # cursor over res_new
                    for i, st in enumerate(sts):
                        rec = None
                        coord = (coords[i] if coords is not None
                                 and i < len(coords) else None)
                        if st in (Status.OK, Status.REJECTED,
                                  Status.NACK_UNCACHED):
                            if track:
                                rec = lane.inflight.pop(slot, None)
                            elif coord is not None:
                                rec = lane.agg_by_coords.pop(coord, None)
                            slot += 1
                        if st == Status.OK:
                            progressed = True
                            if not track:
                                # one results entry lands per device container
                                # (aggregate or singleton): consume the cursor
                                # BEFORE branching so later statuses in this
                                # sweep stay aligned
                                val = (res_new[ri] if ri < len(res_new)
                                       else None)
                                ri += 1
                            if rec is not None and rec.subs is not None:
                                # aggregate container: per-sub-record
                                # completion (cache confirms, individual NACK
                                # rebuilds, one coalesced reply)
                                done += self._complete_agg(
                                    peer, lane, rec,
                                    coord if not track
                                    else lane.mailbox.slot_coords(slot - 1))
                                continue
                            peer.stats["delivered"] += 1
                            done += 1
                            if rec is not None:
                                peer.cached.add(rec.digest)
                                if rec.stream is not None:
                                    # complete: pump off
                                    rec.stream.dead = True
                                o = self.obs
                                if o.enabled:
                                    o.rtt_hist.observe(
                                        (time.monotonic() - rec.sent_at) * 1e6)
                                    if rec.span is not None:
                                        o.tracer.end(rec.span, status="ok")
                                        rec.span = None
                            if not track:
                                ent = (lane.corr_by_coords.pop(coord, None)
                                       if coord is not None else None)
                                if ent:  # device reply: the result IS it
                                    self._route_reply(ent[0], peer.name, val,
                                                      False, decoded=True)
                        elif st == Status.REJECTED:
                            peer.stats["rejected"] += 1
                            done += 1
                            progressed = True
                            if rec is not None:
                                o = self.obs
                                if o.enabled:
                                    o.recorder.add(
                                        "reject", peer.name,
                                        f"{rec.name} corr={rec.corr_id}")
                                    if rec.span is not None:
                                        o.tracer.end(rec.span,
                                                     status="rejected")
                                        rec.span = None
                            if rec is not None and rec.stream is not None:
                                # corrupt stream: ONLY this stream dies —
                                # stop its pump; the scrubbed slot flows on
                                rec.stream.dead = True
                            if rec is not None and rec.subs is not None:
                                # whole container rejected (corrupt aggregate
                                # signal): every corr-carrying record resolves
                                # with the transport error — none executed
                                for sub in rec.subs:
                                    if sub.corr_id:
                                        self._route_reply(
                                            sub.corr_id, peer.name,
                                            TransportError(
                                                "aggregate container "
                                                "rejected"),
                                            True, decoded=True)
                            if not track and coord is not None:
                                ent = lane.corr_by_coords.pop(coord, None)
                                corr = ent[0] if ent else 0
                                if corr:
                                    self._route_reply(
                                        corr, peer.name,
                                        "frame rejected on device sweep",
                                        True, decoded=True)
                        elif st == Status.NACK_UNCACHED:
                            peer.stats["nacks"] += 1
                            self.stats["nacks"] += 1
                            progressed = True
                            if rec is not None:
                                o = self.obs
                                if o.enabled:
                                    o.recorder.add(
                                        "nack", peer.name,
                                        f"{rec.name} corr={rec.corr_id} "
                                        f"slim miss")
                                    if rec.span is not None:
                                        o.tracer.end(rec.span, status="nack")
                                        rec.span = None
                            if rec is not None and rec.stream is not None:
                                # SLIM stream missed the cache at its
                                # descriptor: park the pump and queue a FULL
                                # re-open from chunk 0 (nothing executed)
                                rec.stream.dead = True
                                peer.cached.discard(rec.digest)
                                peer.resend.append(_StreamResend(rec.stream))
                            elif rec is not None and rec.handle is not None:
                                peer.cached.discard(rec.digest)
                                peer.resend.append(
                                    self._rebuild_full(lane, slot - 1, rec))
                            else:
                                # a SLIM frame we have no record/handle
                                # for (raw send): nothing to rebuild —
                                # surface the loss
                                peer.stats["nack_lost"] += 1
                        elif st == Status.IN_PROGRESS:
                            peer.stats["inflight_polls"] += 1
                finally:
                    if sc is not None:
                        sc.set_metadata(n=done - done0)
                        sc.__exit__(None, None, None)
                if peer.stripe:
                    # rotation advances one step per consumed slot, so the
                    # next visit reads the ring the next post landed in
                    peer.stripe_rx += sum(
                        1 for st in sts
                        if st in (Status.OK, Status.REJECTED,
                                  Status.NACK_UNCACHED))
                err = (self._sweep_raise
                       or getattr(lane.mailbox, "pending_raise", None))
                if err is not None:
                    # a corr-less poisoned slot mid-batch (from either the
                    # reply-lane _sweep_task or a plain Mailbox.sweep):
                    # its lane's completed statuses (digest confirms,
                    # aggregate completions, replies) are processed above
                    # — NOW the exception gets its historical visibility
                    self._sweep_raise = None
                    lane.mailbox.pending_raise = None
                    raise err
            self._rr += 1
        self.poll_replies()
        self.stats["polled"] += done
        return done

    def _pending_inflight(self) -> int:
        """Tracked frames still awaiting their target's sweep: host-lane
        inflight records (past-consumed records are pruned as a side
        effect) plus device-lane corr-ids awaiting a sweep result, plus
        coalesced records still queued for an aggregate flush."""
        n = 0
        for peer in self.peers.values():
            for lane in peer.rings:
                low = lane.mailbox.consumed
                for s in [s for s in lane.inflight if s < low]:
                    del lane.inflight[s]
                n += (len(lane.inflight) + len(lane.corr_by_coords)
                      + len(lane.agg_by_coords))
            n += len(peer.resend)
            n += sum(len(q.subs) for q in peer.coalesce.values())
        return n

    def fail_inflight(self, reason: str = "liveness deadline exceeded",
                      min_age: float = 0.0,
                      peers: set | None = None) -> int:
        """Give up on tracked in-flight frames at least ``min_age`` seconds
        old: corr-carrying records resolve their futures with a
        TransportError through the reply router (instead of hanging
        forever on a wedged peer); the records and that peer's queued
        retransmits are dropped.  ``min_age`` is what makes this a *per
        frame* liveness floor — a healthy peer actively consuming its
        backlog only has young records, and keeps them.  ``peers`` scopes
        the pass to named peers (the elastic failure path: ONE peer died;
        everyone else's in-flight work is healthy and must not be touched).
        Returns futures failed."""
        now = time.monotonic()
        failed = 0
        targets = (list(self.peers.values()) if peers is None
                   else [p for n, p in self.peers.items() if n in peers])
        for peer in targets:
            timed_out = 0
            for lane in peer.rings:
                low = lane.mailbox.consumed
                for slot in sorted(lane.inflight):
                    rec = lane.inflight[slot]
                    if slot >= low and now - rec.sent_at < min_age:
                        continue         # young: the peer may still be alive
                    del lane.inflight[slot]
                    o = self.obs
                    if o.enabled and rec.span is not None:
                        o.tracer.end(rec.span, status="failed")
                        rec.span = None
                    if rec.stream is not None:
                        rec.stream.dead = True   # half-arrived stream: the
                        #          pump must never touch the slot again
                        if rec.stream in self._active_streams:
                            self._active_streams.remove(rec.stream)
                    if slot < low:
                        continue
                    if o.enabled:
                        o.recorder.add(
                            "fail_inflight", peer.name,
                            f"{rec.name} corr={rec.corr_id} "
                            f"age={now - rec.sent_at:.3f}s")
                    if rec.subs is not None:
                        for sub in rec.subs:   # aggregate: fail per record
                            if sub.corr_id:
                                self._route_reply(
                                    sub.corr_id, peer.name,
                                    TransportError(
                                        f"{sub.name} (coalesced) to "
                                        f"{peer.name!r}: {reason}"),
                                    True, decoded=True)
                                timed_out += 1
                        continue
                    if not rec.corr_id:
                        continue
                    self._route_reply(
                        rec.corr_id, peer.name,
                        TransportError(
                            f"{rec.name} to {peer.name!r}: {reason} "
                            f"(in flight {now - rec.sent_at:.3f}s)"),
                        True, decoded=True)
                    timed_out += 1
                for coords, (corr, sent_at) in list(
                        lane.corr_by_coords.items()):
                    if now - sent_at < min_age:
                        continue
                    del lane.corr_by_coords[coords]
                    self._route_reply(
                        corr, peer.name,
                        TransportError(
                            f"device lane {peer.name!r}: {reason}"),
                        True, decoded=True)
                    timed_out += 1
                for coords, rec in list(lane.agg_by_coords.items()):
                    if now - rec.sent_at < min_age:
                        continue         # device aggregate: fail per record
                    del lane.agg_by_coords[coords]
                    for sub in rec.subs or ():
                        if sub.corr_id:
                            self._route_reply(
                                sub.corr_id, peer.name,
                                TransportError(
                                    f"{sub.name} (device agg) to "
                                    f"{peer.name!r}: {reason}"),
                                True, decoded=True)
                            timed_out += 1
            if timed_out:
                while peer.resend:       # retransmits to a dead peer: drop
                    msg = peer.resend.popleft()
                    corr = getattr(msg, "corr_id", 0)
                    if corr:
                        self._route_reply(
                            corr, peer.name,
                            TransportError(
                                f"queued retransmit to {peer.name!r}: "
                                f"{reason}"),
                            True, decoded=True)
                        timed_out += 1
                for key in list(peer.coalesce):  # queued coalesced records
                    q = peer.coalesce.pop(key)   # to a dead peer: drop too
                    for sub in q.subs:
                        if sub.corr_id:
                            self._route_reply(
                                sub.corr_id, peer.name,
                                TransportError(
                                    f"queued coalesced {sub.name} to "
                                    f"{peer.name!r}: {reason}"),
                                True, decoded=True)
                            timed_out += 1
                peer.stats["timed_out"] += timed_out
                failed += timed_out
        self.stats["timed_out"] += failed
        if failed:
            o = self.obs
            if o.enabled:
                o.recorder.add("fail_inflight", "",
                               f"{failed} futures failed: {reason}")
                if o.dump_on_fail:
                    o.dump(f"fail_inflight: {reason}")
        return failed

    def drain(self, max_rounds: int = 64, deadline: float | None = None) -> int:
        """flush + poll until quiescent: no outstanding puts, no consumable
        frames, no queued retransmits.  Returns total messages
        delivered/rejected (NACK-retransmitted frames count once, when the
        FULL retry lands).

        ``deadline`` (seconds) is the liveness floor: the drain keeps
        cranking while tracked frames are still in flight (``max_rounds``
        does not apply — the bound is wall time), and once the deadline
        passes it *fails*, via :meth:`fail_inflight`, the futures of
        frames that were in flight for at least the whole deadline —
        frames a peer actively consuming its backlog would have drained.
        Without a deadline, behavior is the historical round-bounded
        quiescence check."""
        t0 = time.monotonic()
        total = 0
        rounds = 0
        while True:
            rounds += 1
            for p in self.peers.values():
                self._flush_resends(p)
                self._flush_coalesce_peer(p)   # drain = explicit flush
            self.engine.progress()
            n = self.poll()
            total += n
            idle = (n == 0 and self.engine.outstanding() == 0
                    and not self._active_streams
                    and not any(p.resend or any(
                        q.subs for q in p.coalesce.values())
                        for p in self.peers.values()))
            if deadline is None:
                if idle or rounds >= max_rounds:
                    break
            else:
                if idle and self._pending_inflight() == 0:
                    break
                if time.monotonic() - t0 >= deadline:
                    self.obs.record(
                        "drain_deadline", "",
                        f"{deadline:.3g}s exceeded, "
                        f"{self._pending_inflight()} frames inflight")
                    self.fail_inflight(
                        f"drain deadline ({deadline:.3g}s) exceeded",
                        min_age=deadline)
                    break
                if idle:
                    time.sleep(0)    # wedged-peer spin: be scheduler-polite
        return total

    # -- reporting ----------------------------------------------------------

    def per_peer_stats(self) -> dict[str, dict]:
        now = time.monotonic()
        return {name: dict(p.stats, credits=p.credits,
                           oldest_inflight_s=round(
                               p.oldest_inflight_age(now), 6))
                for name, p in self.peers.items()}

    def print_stats(self) -> None:
        for p in self.peers.values():
            print(" ", p.summary())


__all__ = ["DEFAULT_N_SLOTS", "DEFAULT_SLOT_SIZE", "Dispatcher", "Peer",
           "RingState"]
