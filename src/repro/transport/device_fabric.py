"""Device-mesh fabric: the on-device mailbox/ppermute ifunc path behind the
same Fabric/Channel/Mailbox contract as the host backends.

The backend wraps ``core/device_mailbox.py``: a mailbox is a ring of
word-frames in (emulated) device memory per mesh shard; a put *transcodes*
the wire byte-frame (header + μVM code + f32 payload + trailer) into the
device word-frame layout — the NIC-offload moment — writing it straight
into its row of the staged generation, one of two host buffers the lane
keeps and fills in turn; flush deposits the staged generation over the
ICI via ``ppermute`` (the RDMA-put analogue); the sweep validates all
slots in one compiled ``ring_poll`` + ``ifunc_vm`` pass with the μVM
program bound at mailbox-open time (the device-side link cache).

Visibility is generation-batched: frames become consumable only after the
depositing flush, which is exactly the in-flight window the ProgressEngine
models on the host fabrics.  Deposits are slot-masked (only written slots
land), so flushing a new generation never clobbers deposited frames a
sweep has not consumed yet.

Kept in its own module so ``repro.transport`` imports without jax.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import frame as F
from repro.obs.trace import Tracer
from repro.transport.fabric import (Channel, Fabric, Mailbox, Swept,
                                    TransportError)


class DeviceMeshMailbox(Mailbox):
    """Ring of word-frame slots on every shard of a 1-D device mesh."""

    #: the ``repro.obs.Obs`` bundle of the dispatcher that opened this lane
    #: (the lane has no target context): its scopes, its channel's, and
    #: ``target.sweep_us`` go there
    obs = None
    #: a sweep consumes whatever deposited frames it finds, so a frame
    #: below ``consumed`` may be one that never arrived
    in_order = False

    @property
    def tracer(self) -> Tracer:
        return Mailbox.tracer if self.obs is None else self.obs.tracer

    def __init__(self, fabric: "DeviceMeshFabric", mesh, axis: str, prog,
                 externals, n_slots: int, n_tiles: int, tile: int = 128,
                 *, shift: int = 0, agg_k: int = 0,
                 prog_name: str | None = None):
        super().__init__()
        import jax

        from repro.core.device_mailbox import (empty_mailbox, make_agg_sweep,
                                               make_deposit, make_sweep,
                                               shard_rows)
        from repro.kernels.ring_poll import HDR_WORDS

        self.fabric = fabric
        self.mesh, self.axis, self.shift = mesh, axis, shift
        self.n_shards = mesh.shape[axis]
        self.n_slots_per_shard = n_slots
        self.n_slots = n_slots * self.n_shards       # dispatcher-visible ring
        self.n_tiles, self.tile = n_tiles, tile
        self.body_words = n_tiles * tile * tile
        self.agg_k = agg_k
        self.prog_name = prog_name
        self.bound_hash = (F.fletcher32(prog_name.encode()) & 0xFFFFFFFF
                           if prog_name else 0)
        if agg_k:
            # aggregate container slot: hdr + K descriptor pairs + K bodies
            # + fixed-tail trailer (kernels/agg_poll.py layout)
            self.slot_words = (HDR_WORDS + 2 * agg_k
                               + agg_k * self.body_words + 1)
            # byte-frame capacity the dispatcher budgets containers against:
            # container header/trailer + counts + per-sub (name-table entry
            # + sub-record + body bytes) + signal
            self.slot_size = (F.HEADER_LEN + F.TRAILER_LEN + 8
                              + agg_k * (33 + F.AGG_SUB_OVERHEAD
                                         + self.body_words * 4) + 4)
        else:
            self.slot_words = HDR_WORDS + self.body_words + 1
            self.slot_size = self.slot_words * 4     # byte-equivalent capacity
        self.prog = prog
        # ring, staged generations and externals ([n_shards, n_ext, T, T])
        # all live shard i on device i of the mesh
        self._sharding = shard_rows(mesh, axis)
        self.externals = jax.device_put(externals, self._sharding)
        self._mb = empty_mailbox(mesh, axis, n_slots, self.slot_words)
        self._deposit = make_deposit(mesh, axis)
        if agg_k:
            self._sweep = make_agg_sweep(mesh, axis, prog, agg_k, n_tiles,
                                         tile, bound_hash=self.bound_hash)
        else:
            self._sweep = make_sweep(mesh, axis, prog, n_tiles, tile)
        # the generation being filled: one of two resident host buffers
        # [n_shards, n_slots_per_shard, slot_words], allocated on first use
        # and then taken in turn, flush by flush (``_open_generation``);
        # None: the next stage takes the next buffer
        self._staged: np.ndarray | None = None
        self._staged_count = 0
        self._staged_fresh = False       # allocated for this generation
        self._gens: list[np.ndarray] = []
        self._gen = 1                    # index of the buffer last taken
        self._carried: list[list] = [[], []]  # (shard, idx) rows per buffer
        self._fences: list = [None, None]     # the deposit that read it
        self._deposited = 0                          # frames awaiting sweep

    @property
    def supports_agg(self) -> bool:
        """Aggregate containers transcode onto this lane (the dispatcher's
        eligibility probe)."""
        return self.agg_k > 0

    # source-side staging (called by DeviceMeshChannel)

    def slot_coords(self, slot: int) -> tuple[int, int]:
        """Dispatcher ring index -> (shard, per-shard slot) interleaving."""
        return slot % self.n_shards, (slot // self.n_shards) % self.n_slots_per_shard

    def _open_generation(self) -> bool:
        """Take the other resident staging buffer as the generation to fill;
        True when it had to be allocated (the first two generations only).

        A reused buffer last went up one flush ago.  ``device_put`` may
        still read it (an asynchronous copy, or the CPU backend's alias of
        the numpy array), so wait for the deposit that read it.  In the
        steady loop a sweep's blocking readback lies between two flushes
        and that deposit is long done; the wait matters only for flushes
        with no sweep between them, and never reaches further back than
        one flush: two buffers are enough.  Then clear the magic and the
        trailer word of every row it carried, so no frame of an earlier
        generation is deposited again (the deposit is masked by word 0)."""
        i = self._gen = 1 - self._gen
        fresh = i == len(self._gens)
        if fresh:
            self._gens.append(np.zeros(
                (self.n_shards, self.n_slots_per_shard, self.slot_words),
                np.uint32))
        else:
            if self._fences[i] is not None:
                import jax

                jax.block_until_ready(self._fences[i])
                self._fences[i] = None
            buf = self._gens[i]
            for shard, idx in self._carried[i]:
                buf[shard, idx, 0] = 0
                buf[shard, idx, -1] = 0
        self._carried[i] = []
        self._staged = self._gens[i]
        self._staged_fresh = fresh
        return fresh

    def _stage(self, slot: int) -> np.ndarray:
        """The row of the generation being filled that ``slot`` stages into
        (a view: the channel writes the word-frame straight into it)."""
        shard, idx = self.slot_coords(slot)
        self._carried[self._gen].append((shard, idx))
        self._staged_count += 1
        return self._staged[shard, idx]

    def _publish(self) -> None:
        """Deposit the staged generation over the ICI (collective_permute):
        the whole generation is copied to the device, however few slots it
        holds.  The buffer stays resident for a later generation."""
        if self._staged is None:
            return
        import jax

        self._mb = self._deposit(self._mb,
                                 jax.device_put(self._staged, self._sharding),
                                 shift=self.shift)
        self._fences[self._gen] = self._mb
        self._deposited += self._staged_count
        self._staged = None
        self._staged_count = 0

    # target side

    def _staged_at(self, shard: int, slot: int) -> tuple[int, int]:
        """Coordinates a frame found at (shard, slot) was staged at."""
        return (shard - self.shift) % self.n_shards, slot

    def slot_view(self, i: int):
        raise TransportError("device mailbox slots live in device memory; "
                             "use sweep()")

    def sweep(self, ctx, target_args, budget: int | None = None):
        """One compiled validate+execute pass over every deposited slot;
        ``(completions, None)`` as on a host lane (no ifunc raises here).
        ``budget`` is ignored: the sweep is a single device program, so a
        device lane may yield more than one message per dispatcher poll
        round (its yield still counts against the caller's total budget).
        A completion's coordinate is where its frame was *staged* (a
        deposit lands ``shift`` shards on)."""
        if self._deposited == 0:
            return [], None
        o = self.obs
        t0 = time.perf_counter() if o is not None and o.enabled else None
        consumed0 = self.consumed
        done = (self._sweep_agg if self.agg_k
                else self._sweep_one)(target_args)
        if t0 is not None and self.consumed != consumed0:
            # as on a host lane: only sweeps that consumed something observe
            o.sweep_hist.observe((time.perf_counter() - t0) * 1e6)
        return done, None

    def _run_sweep(self) -> list:
        """The jitted sweep over the ring, its outputs read back to the
        host (blocking); the cleared ring stays on the device."""
        with self.tracer.scope("repro.device.readback") as sc:
            *outs, self._mb = self._sweep(self._mb, self.externals)
            host = [np.asarray(a) for a in outs]
            sc.set_metadata(bytes=sum(h.nbytes for h in host))
        # the outputs are on the host, so every deposit before this sweep
        # is done with its staging buffer: drop the rings that fenced them
        self._fences = [None, None]
        return host

    def _demux(self, status, ready) -> list:
        """One :class:`Swept` per slot the sweep found a frame in, with
        ``ready(c, shard, slot)`` filling in a READY one; then advance the
        consume counters past the OK/REJECTED ones."""
        from repro.core.api import Status
        from repro.kernels.ring_poll import BAD, INFLIGHT, READY

        as_status = {READY: Status.OK, BAD: Status.REJECTED,
                     INFLIGHT: Status.IN_PROGRESS}
        with self.tracer.scope("repro.device.demux") as sc:
            done: list[Swept] = []
            for shard in range(status.shape[0]):
                for slot in range(status.shape[1]):
                    st = as_status.get(int(status[shard, slot]))
                    if st is None:
                        continue                     # EMPTY
                    c = Swept(self._staged_at(shard, slot), st)
                    if st is Status.OK:
                        ready(c, shard, slot)
                    elif st is Status.REJECTED:
                        c.error = TransportError("frame rejected on device "
                                                 "sweep")
                    done.append(c)
            consumed = sum(1 for c in done if c.status is not
                           Status.IN_PROGRESS)
            self.head += consumed
            self.consumed += consumed
            self._deposited = max(self._deposited - consumed, 0)
            sc.set_metadata(n=consumed)
        return done

    def _sweep_one(self, target_args) -> list:
        from repro.core.api import collect_result
        from repro.kernels.ring_poll import READY

        status, out = self._run_sweep()
        copy = _sparse(int((status == READY).sum()), status.size)

        def ready(c, shard, slot):
            c.value = out[shard, slot].copy() if copy else out[shard, slot]
            collect_result(target_args, c.value)
        return self._demux(status, ready)

    def _sweep_agg(self, target_args) -> list:
        """Aggregate-container sweep: one batched kernel pass validates all
        containers + descriptors and ONE μVM launch executes every
        sub-record body; each READY container's completion carries its
        per-sub outcomes, so the dispatcher completes them with host-lane
        semantics (per-sub NACK rebuild, poisoned sub = ERR with siblings
        unharmed, corrupt container = whole REJECT)."""
        from repro.core.api import AggSubResult, Status, collect_result
        from repro.kernels.agg_poll import SUB_BAD, SUB_EMPTY, SUB_READY

        status, sub_st, out = self._run_sweep()
        copy = _sparse(int((sub_st == SUB_READY).sum()), sub_st.size)

        def ready(c, shard, slot):
            c.subs = []
            for i in range(self.agg_k):
                s_i = int(sub_st[shard, slot, i])
                if s_i == SUB_EMPTY:
                    break
                if s_i == SUB_READY:
                    val = out[shard, slot, i]
                    val = val.copy() if copy else val
                    c.subs.append(AggSubResult(Status.OK, "", b"", 0,
                                               value=val))
                    collect_result(target_args, val)
                elif s_i == SUB_BAD:
                    c.subs.append(AggSubResult(
                        Status.REJECTED, "", b"", 0, error=TransportError(
                            "poisoned sub-record (descriptor check "
                            "mismatch)")))
                else:                            # SUB_NACK
                    c.subs.append(AggSubResult(Status.NACK_UNCACHED, "",
                                               b"", 0))
            if len(c.subs) == 1:
                # maybe a transcoded singleton: its answer is its one sub's
                c.value, c.error = c.subs[0].value, c.subs[0].error
        return self._demux(status, ready)


def _sparse(n_answers: int, capacity: int) -> bool:
    """Whether a sweep's answers leave its readback as copies: where they
    fill less than half of it.  A caller may keep an answer as long as it
    likes (a future's result), and a view keeps the whole readback alive:
    for a lane that finds one frame a sweep, a thousand times the answer's
    own bytes.  Where they fill it, views cost nothing more."""
    return 2 * n_answers < capacity


def _agg_bodies(batch: F.AggBatch, body_words: int):
    """A container's sub-record bodies, as zero-copy views of the frame,
    and their name hashes; every sub-record must be a μVM one of the bound
    size."""
    uvm = int(F.CodeKind.UVM)
    for i, (k, plen) in enumerate(zip(batch.kinds, batch.plens)):
        if k != uvm:
            raise TransportError("device mesh accepts UVM sub-records only, "
                                 f"got {batch.kind(i).name}")
        if plen != 4 * body_words:
            raise TransportError(f"device agg sub payload {plen // 4} words "
                                 f"!= bound {body_words}")
    by_name = [F.fletcher32(nm.encode()) & 0xFFFFFFFF for nm in batch.names]
    hashes = [by_name[j] for j in batch.name_idx]
    if batch.n and not any(batch.clens):
        # no continuations: the bodies lie back to back in the payload
        # region, so they go into the slot in one copy
        return np.frombuffer(batch.mv, np.float32, count=batch.n * body_words,
                             offset=batch.starts[0]).reshape(
                                 batch.n, body_words), hashes
    return [np.frombuffer(batch.payload(i), np.float32)
            for i in range(batch.n)], hashes


class DeviceMeshChannel(Channel):
    def __init__(self, mailbox: DeviceMeshMailbox):
        super().__init__()
        self.mailbox = mailbox
        self.stats["gen_allocs"] = 0     # staging generation buffers made

    def put(self, data, slot: int, *, deliver_bytes: int | None = None) -> None:
        """Transcode a wire byte-frame into the device word-frame layout and
        stage it.  ``deliver_bytes`` short of the full frame stages the
        word-frame without its trailer word (the device-visible in-flight
        state); flush completes trailers before depositing.

        SLIM-aware: the μVM program is bound at mailbox-open time (the
        device-side link cache), so code words are *never* deposited over
        the ICI — a SLIM frame (code elided at the source) transcodes
        identically to a FULL one, and the payload is read through a
        zero-copy section view straight out of the sender's slab."""
        with self.mailbox.tracer.scope("repro.device.transcode",
                                       bytes=len(data)) as sc:
            sc.set_metadata(n=self._transcode(data, slot, deliver_bytes))
        self.stats["puts"] += 1
        self.stats["bytes"] += len(data)

    def _transcode(self, data, slot: int, deliver_bytes: int | None) -> int:
        """Parse, then pack the word-frame straight into its staged row;
        returns the number of invocations the frame carries."""
        from repro.core.device_mailbox import pack_agg_word_frame, pack_word_frame

        mb = self.mailbox
        hdr = F.peek_header(data)
        if hdr is None:
            raise TransportError("device put of an empty frame")
        partial = deliver_bytes is not None and deliver_bytes < len(data)
        if hdr.is_agg:
            if not getattr(mb, "supports_agg", False):
                # without an agg_k bind the slot has no descriptor table or
                # per-sub body lanes: containers need an agg-bound mailbox
                raise TransportError(
                    "aggregate frame on a device mailbox opened without "
                    "agg_k= — bind an aggregate slot layout first")
            _, payload = F.frame_sections(data, hdr)
            try:
                batch = F.parse_agg(payload)
            except F.FrameError as e:
                raise TransportError(f"device agg transcode: {e}") from e
            pays, hashes = _agg_bodies(batch, mb.body_words)
            pack_agg_word_frame(pays, hashes, mb.agg_k, mb.body_words,
                                mb.slot_words, kind=int(hdr.code_kind),
                                no_trailer=partial, out=self._row(slot))
        else:
            if hdr.code_kind != F.CodeKind.UVM:
                raise TransportError(
                    f"device mesh accepts UVM frames only, got "
                    f"{hdr.code_kind.name}")
            _, payload = F.frame_sections(data, hdr)
            tiles = np.frombuffer(payload, np.float32)
            want = mb.body_words
            if tiles.size != want:
                raise TransportError(
                    f"device frame payload {tiles.size} words != bound "
                    f"{want} ({mb.n_tiles} x {mb.tile}x{mb.tile} tiles)")
            if getattr(mb, "supports_agg", False):
                # singleton on an agg-bound lane: a degenerate 1-sub
                # container.  The descriptor carries the *bound* hash — the
                # non-agg device path never name-checks (the program is
                # linked at open), and the per-sub NACK is an aggregate
                # concept (there is a handle to rebuild from); parity kept.
                pack_agg_word_frame(
                    [tiles], [mb.bound_hash], mb.agg_k, mb.body_words,
                    mb.slot_words, kind=int(hdr.code_kind),
                    no_trailer=partial, out=self._row(slot))
            else:
                name_hash = F.fletcher32(hdr.name.encode()) & 0xFFFFFFFF
                pack_word_frame(tiles, mb.slot_words, kind=int(hdr.code_kind),
                                name_hash=name_hash, no_trailer=partial,
                                out=self._row(slot))
        if partial:
            from repro.kernels.ring_poll import HDR_WORDS, TRAILER

            word_idx = (mb.slot_words - 1 if getattr(mb, "agg_k", 0)
                        else HDR_WORDS + mb.body_words)
            self._pending_trailers = getattr(self, "_pending_trailers", [])
            self._pending_trailers.append((slot, word_idx, TRAILER))
            self.stats["partial"] += 1
        return batch.n if hdr.is_agg else 1

    def _row(self, slot: int) -> np.ndarray:
        """The staged row ``slot`` is written into, taking the next resident
        generation buffer when none is being filled."""
        mb = self.mailbox
        if mb._staged is None:
            self.stats["gen_allocs"] += mb._open_generation()
        return mb._stage(slot)

    def flush(self) -> None:
        mb = self.mailbox
        staged = mb._staged
        with mb.tracer.scope(
                "repro.device.publish", n=mb._staged_count,
                bytes=0 if staged is None else staged.nbytes,
                fresh=int(staged is not None and mb._staged_fresh)):
            for slot, word_idx, trailer in getattr(self, "_pending_trailers",
                                                   []):
                shard, idx = mb.slot_coords(slot)
                if staged is not None:
                    staged[shard, idx, word_idx] = trailer
            self._pending_trailers = []
            mb._publish()
        self.stats["flushes"] += 1


class DeviceMeshFabric(Fabric):
    """TPU-tier backend: open_mailbox binds a μVM program + external table
    (the device GOT) to a compiled deposit/sweep pair on a 1-D mesh axis."""

    kind = "device"

    def __init__(self, mesh, axis: str = "model", *, shift: int = 0):
        self.mesh, self.axis, self.shift = mesh, axis, shift

    def open_mailbox(self, target_ctx, n_slots: int, slot_size: int,
                     *, prog=None, externals=None, n_tiles: int = 1,
                     tile: int = 128, agg_k: int = 0,
                     prog_name: str | None = None) -> DeviceMeshMailbox:
        """``target_ctx`` is unused (the mesh is the target); ``slot_size``
        must cover the bound word-frame.  ``prog``/``externals`` bind the
        μVM program — required (the device links at mailbox-open time).
        ``agg_k > 0`` binds the *aggregate container* slot layout (K
        sub-record bodies per slot, batched agg_poll sweep) and marks the
        lane coalesce-eligible; ``prog_name`` bounds sub-record name hashes
        (mismatches NACK per sub, None = wildcard)."""
        if prog is None:
            raise TransportError("DeviceMeshFabric.open_mailbox needs prog=")
        n_shards = self.mesh.shape[self.axis]
        if externals is None:
            externals = np.zeros((n_shards, max(prog.n_ext, 1), tile, tile),
                                 np.float32)
        mb = DeviceMeshMailbox(self, self.mesh, self.axis, prog, externals,
                               n_slots, n_tiles, tile, shift=self.shift,
                               agg_k=agg_k, prog_name=prog_name)
        if slot_size < mb.slot_size:
            raise TransportError(
                f"slot_size {slot_size} < device word-frame {mb.slot_size}B")
        return mb

    def connect(self, src_ctx, mailbox: DeviceMeshMailbox) -> DeviceMeshChannel:
        return DeviceMeshChannel(mailbox)


__all__ = ["DeviceMeshChannel", "DeviceMeshFabric", "DeviceMeshMailbox"]
