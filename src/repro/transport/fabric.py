"""Pluggable fabric layer: the one transport contract both ifunc universes
(host RDMA emulation and the on-device mailbox/ppermute path) sit on.

Three roles, mirroring a thin UCX:

* :class:`Mailbox`  — a target-owned ring of fixed-size frame slots.  The
  host fabrics expose byte slots polled by ``poll_ifunc``; the device
  fabric exposes word-frame slots swept by the ``ring_poll`` Pallas kernel.
* :class:`Channel`  — a source-side one-sided path into one mailbox.  A
  ``put`` is *non-blocking*: bytes may be partially visible until
  ``flush`` (the real-RDMA in-flight window the frame trailer exists for).
* :class:`Fabric`   — the factory tying the two together for one backend.

Backends here: :class:`RdmaFabric` (wraps ``core/rdma.py``) and
:class:`LoopbackFabric` (zero-copy in-process, for tests/benchmarks and
"CSD-attached" targets).  :class:`DeviceMeshFabric` lives in
``device_fabric.py`` so importing the transport core never drags in jax.

Invariant enforced by this package: nothing outside ``repro.transport``
calls ``Endpoint.put_nbi`` — higher layers (``core/api.py``, the
dispatcher, the pod controller, serving) speak Channel/Mailbox only.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from repro.core import frame as F
from repro.core import rdma as R
from repro.obs.trace import Tracer


class TransportError(Exception):
    pass


_API = None      # repro.core.api, imported lazily (api imports codegen,
#                  which the transport core must not drag in at import
#                  time) and memoized — the sweep hot loop must not pay
#                  the import machinery per call


def _api():
    global _API
    if _API is None:
        from repro.core import api
        _API = api
    return _API


# ---------------------------------------------------------------------------
# contracts


@dataclass(slots=True)
class Swept:
    """What a sweep found at one slot that held a frame: the one record a
    target hands its dispatcher, on every fabric.

    ``coord`` is the slot's :meth:`Mailbox.slot_coords` coordinate, the
    key of the dispatcher's in-flight table.  ``corr``, ``name`` and
    ``kind`` are the frame header's, read before execution (a device
    frame carries none: its dispatcher knows them).  ``value`` is the
    ifunc's answer: ``target_args["result"]`` of a corr-carrying host
    frame, the output of a device frame.  ``subs`` holds an aggregate's
    per-sub-record ``api.AggSubResult`` outcomes.  ``error`` is what a
    corr-carrying frame's ifunc raised (status OK: delivered, and the
    error is its answer) or why a frame was rejected."""

    coord: object
    status: object                      # repro.core.api.Status
    corr: int = 0
    name: str = ""
    kind: object = None                 # frame.CodeKind
    value: object = None
    subs: list | None = None
    error: BaseException | None = None


class Mailbox:
    """A target-owned ring of ``n_slots`` frame slots of ``slot_size`` bytes.

    ``head`` is the consume index (advanced by the poller); the produce index
    lives with the source-side Channel.  ``consumed`` is the monotone count
    of drained slots — the source reads it to compute returned credits (the
    emulation analogue of a credit-return counter the target writes back).
    :meth:`sweep` returns one :class:`Swept` per frame it reached,
    keyed by :meth:`slot_coords`: the whole hand-off from target to
    dispatcher.
    """

    fabric: "Fabric"
    n_slots: int
    slot_size: int
    #: what the dispatcher scopes this lane's completions with: a tracer
    #: never enabled, so a host lane keeps no span in memory (a profiler
    #: session still records the scope); a lane that traces its own
    #: layers (the device lane) returns its dispatcher's
    tracer = Tracer()
    #: slots are consumed in produce order (a ring): a frame below
    #: ``consumed`` is done, whoever swept it
    in_order = True

    def __init__(self):
        self.head = 0
        self.consumed = 0
        #: in-progress FLAG_STREAM receive state, keyed by the slot
        #: coordinate the stream frame occupies: coordinate ->
        #: ``api._StreamRx``.  Owned by the poll core (created on the
        #: descriptor's arrival, popped at completion/rejection); the
        #: mailbox carries it because stream lifetime spans many sweeps
        #: of one slot.  ``stream_consumed`` exposes the consume counter
        #: the source's chunk pump reads for window flow control.
        self.streams: dict = {}

    def slot_coords(self, i: int):
        """Stable coordinate a produce-index maps to (what a
        :class:`Swept` is keyed by).  Identity for in-order host
        rings."""
        return i

    def stream_consumed(self, coords) -> int:
        """Number of chunks the stream at ``coords`` has consumed — the
        credit-return counter the source's chunk pump polls before
        overwriting a window cell (a cell is reusable once the chunk
        ``window`` positions behind it has been consumed).  0 until the
        stream descriptor has been polled."""
        rx = self.streams.get(coords)
        return 0 if rx is None else rx.next_seq

    def slot_view(self, i: int) -> memoryview:
        raise NotImplementedError

    def peek(self):
        """Best-effort parsed header of the frame at ``head``, or None when
        the slot is empty/unparsable or the backend exposes no byte view
        (the device mesh).  Part of the fabric contract: the dispatcher's
        reply and flow paths read corr/flags *ahead of* the consuming sweep
        through this instead of duck-typing into backend internals —
        corruption surfaces later, on the sweep itself."""
        try:
            return F.peek_header(self.slot_view(self.head))
        except (F.FrameError, TransportError, NotImplementedError):
            return None

    def drop_head(self) -> None:
        """Consume the slot at ``head`` whatever it holds (an ifunc raised
        there): scrub it, and end the stream that held it, if any."""
        F.scrub_slot(self.slot_view(self.head))
        self.streams.pop(self.slot_coords(self.head), None)
        self.head += 1
        self.consumed += 1

    def sweep(self, ctx, target_args, budget: int | None = None):
        """Drain up to ``budget`` slots through the poll core.  Returns
        ``(completions, raised)``: one :class:`Swept` per slot that
        held a frame, in consume order, and the exception an ifunc raised
        at the slot after the last of them, or None.

        OK/REJECTED/NACK_UNCACHED consume the slot and advance head (a
        NACKed SLIM frame is cleared — the retransmit arrives as a fresh
        FULL frame); IN_PROGRESS and an empty slot end the batch.  An ifunc
        that raises on a corr-carrying frame has an answer to carry it: the
        slot is consumed and its completion (status OK) holds the error.
        One that raises on a corr-less frame ends the batch with the slot
        left as it is; the caller raises ``raised`` once it has processed
        the completions before it.

        Note: the *recovery* half of the NACK protocol (rebuilding and
        requeueing the FULL frame) lives in ``Dispatcher.poll``; a caller
        sweeping a mailbox directly must either send FULL frames only (the
        default until a dispatcher confirms the peer) or handle
        NACK_UNCACHED in the returned completions itself."""
        A = _api()
        Status = A.Status

        done: list[Swept] = []
        raised = None
        is_dict = isinstance(target_args, dict)
        budget = self.n_slots if budget is None else budget
        obs = getattr(ctx, "obs", None)
        t0 = (time.perf_counter() if obs is not None and obs.enabled
              else None)
        consumed0 = self.consumed
        for _ in range(budget):
            coord = self.slot_coords(self.head)
            hdr = self.peek()                # what execution may destroy
            c = Swept(coord, Status.OK)
            if hdr is not None:
                c.corr, c.name, c.kind = hdr.corr_id, hdr.name, hdr.code_kind
            if c.corr and is_dict:
                target_args.pop("result", None)
            try:
                c.status, c.subs = A._poll(
                    ctx, self.slot_view(self.head), target_args,
                    self.streams, coord)
            except Exception as e:           # raised *inside* an ifunc
                if not c.corr:
                    raised = e               # no future to carry it
                    break
                self.drop_head()             # delivered: the error answers
                c.error = e
                done.append(c)
                continue
            st = c.status
            if st is Status.NO_MESSAGE:
                break
            if c.corr:
                if st is Status.OK:
                    c.value = target_args.get("result") if is_dict else None
                elif st is Status.REJECTED:
                    c.error = TransportError(
                        ctx.stats.get("last_reject", "frame rejected"))
            done.append(c)
            if st is Status.IN_PROGRESS:
                break
            self.head += 1
            self.consumed += 1
        if t0 is not None and self.consumed != consumed0:
            # only sweeps that consumed something observe: idle polls would
            # otherwise flood the distribution with empty-peek latencies
            obs.sweep_hist.observe((time.perf_counter() - t0) * 1e6)
        return done, raised


class Channel:
    """Source-side one-sided path into one remote Mailbox."""

    mailbox: Mailbox

    def __init__(self):
        self.stats = {"puts": 0, "bytes": 0, "flushes": 0, "partial": 0}

    def put(self, data, slot: int, *, deliver_bytes: int | None = None) -> None:
        """Non-blocking write of ``data`` into ring slot ``slot``.  With
        ``deliver_bytes`` only a prefix is visible until :meth:`flush` —
        the ProgressEngine uses this to model in-flight puts."""
        raise NotImplementedError

    def put_at(self, data, slot: int, offset: int, *,
               deliver_bytes: int | None = None) -> None:
        """Non-blocking write of ``data`` at byte ``offset`` *within* ring
        slot ``slot`` — the streamed-payload path's chunk put (and the
        stream open's withheld frame trailer).  Same delivery semantics as
        :meth:`put`; ``deliver_bytes=0`` withholds the entire write until
        :meth:`flush` (a chunk seal / trailer barrier).  Backends without
        sub-slot addressing (the device mesh) don't implement it — streams
        are a host-tier feature, like continuations."""
        raise NotImplementedError

    def putv_at(self, segs, slot: int, *, withhold_tail: int = 0) -> None:
        """Scatter-gather write into ring slot ``slot``: ``segs`` is a
        sequence of ``(offset, data)`` pairs posted as ONE work request.
        ``withhold_tail`` keeps the last N bytes of the final segment
        invisible until :meth:`flush` — callers order the barrier bytes
        (frame trailer, chunk seal) last.  The generic fallback degrades
        to one :meth:`put_at` per segment; RDMA-class backends override
        with a true multi-SGE posting."""
        last = len(segs) - 1
        for i, (off, d) in enumerate(segs):
            db = None
            if withhold_tail and i == last:
                db = max(len(d) - withhold_tail, 0)
            self.put_at(d, slot, off, deliver_bytes=db)

    def flush(self) -> None:
        raise NotImplementedError


class Fabric:
    """One transport backend: makes mailboxes on targets, channels to them."""

    kind: str = "abstract"

    def open_mailbox(self, target_ctx, n_slots: int, slot_size: int) -> Mailbox:
        raise NotImplementedError

    def connect(self, src_ctx, mailbox: Mailbox) -> Channel:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# RDMA fabric (wraps core/rdma.py)


class RdmaMailbox(Mailbox):
    """Composes the existing rdma.RingBuffer for all slot math."""

    def __init__(self, fabric: "RdmaFabric", region: R.MemRegion, slot_size: int):
        super().__init__()
        self.fabric = fabric
        self.region = region
        self.ring = R.RingBuffer(region, slot_size)
        self.slot_size = slot_size
        self.n_slots = self.ring.n_slots

    def slot_addr(self, i: int) -> int:
        return self.ring.slot_addr(i)

    def slot_view(self, i: int) -> memoryview:
        return self.ring.slot_view(i)


class RdmaChannel(Channel):
    def __init__(self, ep: R.Endpoint, mailbox: RdmaMailbox):
        super().__init__()
        self.ep = ep
        self.mailbox = mailbox

    def put(self, data, slot: int, *, deliver_bytes: int | None = None) -> None:
        if len(data) > self.mailbox.slot_size:
            raise TransportError(
                f"frame {len(data)}B exceeds slot {self.mailbox.slot_size}B")
        self.ep.put_nbi(data, self.mailbox.slot_addr(slot),
                        self.mailbox.region.rkey, deliver_bytes=deliver_bytes)
        self.stats["puts"] += 1
        self.stats["bytes"] += len(data)
        if deliver_bytes is not None and deliver_bytes < len(data):
            self.stats["partial"] += 1

    def put_at(self, data, slot: int, offset: int, *,
               deliver_bytes: int | None = None) -> None:
        if offset + len(data) > self.mailbox.slot_size:
            raise TransportError(
                f"put_at [{offset}, {offset + len(data)}) exceeds slot "
                f"{self.mailbox.slot_size}B")
        self.ep.put_nbi(data, self.mailbox.slot_addr(slot) + offset,
                        self.mailbox.region.rkey, deliver_bytes=deliver_bytes)
        self.stats["puts"] += 1
        self.stats["bytes"] += len(data)
        if deliver_bytes is not None and deliver_bytes < len(data):
            self.stats["partial"] += 1

    def putv_at(self, segs, slot: int, *, withhold_tail: int = 0) -> None:
        extent = 0
        nbytes = 0
        for off, d in segs:
            nbytes += len(d)
            end = off + len(d)
            extent = end if end > extent else extent
        if extent > self.mailbox.slot_size:
            raise TransportError(
                f"putv extent {extent}B exceeds slot "
                f"{self.mailbox.slot_size}B")
        self.ep.putv_nbi(segs, self.mailbox.slot_addr(slot),
                         self.mailbox.region.rkey,
                         withhold_tail=withhold_tail)
        self.stats["puts"] += 1
        self.stats["bytes"] += nbytes
        if withhold_tail:
            self.stats["partial"] += 1

    def put_raw(self, data, remote_addr: int, rkey: int, *,
                deliver_bytes: int | None = None) -> None:
        """Address-directed put for legacy callers (``ifunc_msg_send_nbix``
        with an explicit remote_addr/rkey, the AM baseline's eager slots)."""
        self.ep.put_nbi(data, remote_addr, rkey, deliver_bytes=deliver_bytes)
        self.stats["puts"] += 1
        self.stats["bytes"] += len(data)

    def flush(self) -> None:
        self.ep.flush()
        self.stats["flushes"] += 1


class RdmaFabric(Fabric):
    """Emulated-RDMA backend: mailboxes are ``mem_map``-ed regions, channels
    are NIC endpoints; every inbound put is rkey/bounds-checked by the
    'HCA' before any byte moves."""

    kind = "rdma"

    def open_mailbox(self, target_ctx, n_slots: int, slot_size: int) -> RdmaMailbox:
        nic = target_ctx.nic if hasattr(target_ctx, "nic") else target_ctx
        region = nic.mem_map(n_slots * slot_size)
        return RdmaMailbox(self, region, slot_size)

    def connect(self, src_ctx, mailbox: RdmaMailbox) -> RdmaChannel:
        nic = src_ctx.nic if hasattr(src_ctx, "nic") else src_ctx
        return RdmaChannel(nic.connect(mailbox.region.nic), mailbox)

    @staticmethod
    def channel_for_endpoint(ep: R.Endpoint) -> "RdmaChannel":
        """Wrap a bare Endpoint for address-directed legacy sends (no ring)."""
        ch = RdmaChannel.__new__(RdmaChannel)
        Channel.__init__(ch)
        ch.ep = ep
        ch.mailbox = None
        return ch


# ---------------------------------------------------------------------------
# Loopback fabric (zero-copy in-process; the "CSD" / test backend)


@dataclass
class _PendingLoopPut:
    buf: bytearray
    off: int            # where the withheld tail lands at flush
    tail: bytes


class LoopbackMailbox(Mailbox):
    def __init__(self, fabric: "LoopbackFabric", n_slots: int, slot_size: int):
        super().__init__()
        self.fabric = fabric
        self.n_slots, self.slot_size = n_slots, slot_size
        self.buf = bytearray(n_slots * slot_size)

    def slot_view(self, i: int) -> memoryview:
        off = (i % self.n_slots) * self.slot_size
        return memoryview(self.buf)[off:off + self.slot_size]


class LoopbackChannel(Channel):
    def __init__(self, mailbox: LoopbackMailbox):
        super().__init__()
        self.mailbox = mailbox
        self._pending: list[_PendingLoopPut] = []

    def put(self, data, slot: int, *, deliver_bytes: int | None = None) -> None:
        mb = self.mailbox
        nd = len(data)
        if nd > mb.slot_size:
            raise TransportError(
                f"frame {nd}B exceeds slot {mb.slot_size}B")
        off = (slot % mb.n_slots) * mb.slot_size
        mv = data if isinstance(data, memoryview) else memoryview(data)
        n = nd if deliver_bytes is None else min(deliver_bytes, nd)
        mb.buf[off:off + n] = mv[:n]
        if n < nd:
            self._pending.append(_PendingLoopPut(mb.buf, off + n, bytes(mv[n:])))
            self.stats["partial"] += 1
        self.stats["puts"] += 1
        self.stats["bytes"] += nd

    def put_at(self, data, slot: int, offset: int, *,
               deliver_bytes: int | None = None) -> None:
        mb = self.mailbox
        nd = len(data)
        if offset + nd > mb.slot_size:
            raise TransportError(
                f"put_at [{offset}, {offset + nd}) exceeds slot "
                f"{mb.slot_size}B")
        off = (slot % mb.n_slots) * mb.slot_size + offset
        mv = data if isinstance(data, memoryview) else memoryview(data)
        n = nd if deliver_bytes is None else min(deliver_bytes, nd)
        if n:
            mb.buf[off:off + n] = mv[:n]
        if n < nd:
            self._pending.append(_PendingLoopPut(mb.buf, off + n, bytes(mv[n:])))
            self.stats["partial"] += 1
        self.stats["puts"] += 1
        self.stats["bytes"] += nd

    def putv_at(self, segs, slot: int, *, withhold_tail: int = 0) -> None:
        mb = self.mailbox
        base = (slot % mb.n_slots) * mb.slot_size
        last = len(segs) - 1
        nbytes = 0
        for i, (off, d) in enumerate(segs):
            mv = d if isinstance(d, memoryview) else memoryview(d)
            nd = len(mv)
            nbytes += nd
            if off + nd > mb.slot_size:
                raise TransportError(
                    f"putv [{off}, {off + nd}) exceeds slot "
                    f"{mb.slot_size}B")
            n = max(nd - withhold_tail, 0) if withhold_tail and i == last \
                else nd
            if n:
                mb.buf[base + off:base + off + n] = mv[:n]
            if n < nd:
                self._pending.append(
                    _PendingLoopPut(mb.buf, base + off + n, bytes(mv[n:])))
                self.stats["partial"] += 1
        self.stats["puts"] += 1
        self.stats["bytes"] += nbytes

    def flush(self) -> None:
        for p in self._pending:
            p.buf[p.off:p.off + len(p.tail)] = p.tail
        self._pending.clear()
        self.stats["flushes"] += 1


class LoopbackFabric(Fabric):
    """In-process zero-copy backend: no NIC, no rkeys — the floor every
    latency number should be compared against, and the stand-in for
    bus-attached targets (CSDs) whose 'network' is a memory bus."""

    kind = "loopback"

    def open_mailbox(self, target_ctx, n_slots: int, slot_size: int) -> LoopbackMailbox:
        return LoopbackMailbox(self, n_slots, slot_size)

    def connect(self, src_ctx, mailbox: LoopbackMailbox) -> LoopbackChannel:
        return LoopbackChannel(mailbox)


class LegacyRingMailbox(Mailbox):
    """Adapter: an existing ``rdma.RingBuffer`` viewed as a transport
    Mailbox, so the deprecated ``poll_ring`` API drains through the same
    sweep path as everything else.  Head state stays on the RingBuffer."""

    def __init__(self, ring: R.RingBuffer):
        Mailbox.__init__(self)
        self.ring = ring
        self.n_slots = ring.n_slots
        self.slot_size = ring.slot_size

    @property
    def head(self) -> int:
        return self.ring.head

    @head.setter
    def head(self, v: int) -> None:
        # Mailbox.__init__ assigns head=0 before self.ring exists; swallow it.
        if hasattr(self, "ring"):
            self.ring.head = v

    def slot_view(self, i: int) -> memoryview:
        return self.ring.slot_view(i)


def ring_mailbox(ring: R.RingBuffer) -> LegacyRingMailbox:
    """Cached LegacyRingMailbox for a RingBuffer (keeps ``consumed`` stable
    across calls so credit math works)."""
    mb = getattr(ring, "_transport_mailbox", None)
    if mb is None:
        mb = LegacyRingMailbox(ring)
        ring._transport_mailbox = mb
    return mb


def endpoint_channel(ep: R.Endpoint) -> RdmaChannel:
    """Cached raw channel for a bare Endpoint (legacy address-directed
    sends route through the transport layer via this)."""
    ch = getattr(ep, "_transport_channel", None)
    if ch is None:
        ch = RdmaFabric.channel_for_endpoint(ep)
        ep._transport_channel = ch
    return ch


def frame_fits(frame, mailbox: Mailbox) -> bool:
    return len(frame) <= mailbox.slot_size


__all__ = [
    "Channel", "Fabric", "LegacyRingMailbox", "Mailbox", "TransportError",
    "LoopbackChannel", "LoopbackFabric", "LoopbackMailbox",
    "RdmaChannel", "RdmaFabric", "RdmaMailbox",
    "Swept", "endpoint_channel", "frame_fits", "ring_mailbox",
]
