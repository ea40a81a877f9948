"""Streamed large-payload transport (frame v2.5 FLAG_STREAM).

Covers the wire format (descriptor/chunk round trips, legality, the
pending-vs-corrupt peek order), the blockwise vectorized fletcher32
against the pure-Python oracle, the per-peer wire codecs, the dispatcher
stream lifecycle end to end (exec-on-arrival past the window, buffered
assembly for non-streaming ifuncs, auto-routing above the threshold,
SLIM->NACK->FULL rebuild exactly once), the failure modes (corrupt chunk
rejects only its stream; fail_inflight / drain(deadline=) resolve a
half-arrived stream's future), and the striping-aware placement pricing.
"""

import struct

import numpy as np
import pytest

import repro.core.frame as F
import repro.transport.codec as WC
from repro.core import Context, Status, ifunc_msg_create, register_ifunc
from repro.transport import (Dispatcher, LoopbackFabric, ProgressEngine,
                             RdmaFabric, TransportError)
from repro.tasks.placement import PlacementEngine


def _mk(lib_dir, *, n_slots=4, slot_size=32 << 10, fabric=None, **peer_kw):
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, ProgressEngine(flush_threshold=64))
    d.add_peer("p", fabric if fabric is not None else RdmaFabric(),
               Context("p", lib_dir=lib_dir, link_mode="remote"),
               n_slots=n_slots, slot_size=slot_size,
               target_args={"db": []}, **peer_kw)
    return d


# ---------------------------------------------------------------------------
# wire format


def test_stream_flag_legality():
    pay = bytes(F.STREAM_DESC_LEN)
    for bad in (F.FLAG_REPLY, F.FLAG_AGG):
        buf = F.pack_frame("x", b"", pay, F.CodeKind.PYBC,
                           flags=F.FLAG_STREAM | bad)
        with pytest.raises(F.FrameError, match="request singletons"):
            F.peek_header(buf)
    buf = F.pack_frame("x", b"", pay, F.CodeKind.PYBC,
                       flags=F.FLAG_STREAM, cont=b"\x01\x02")
    with pytest.raises(F.FrameError, match="request singletons"):
        F.peek_header(buf)
    # undersized payload section: smaller than the descriptor itself
    buf = F.pack_frame("x", b"", b"\x00" * (F.STREAM_DESC_LEN - 1),
                       F.CodeKind.PYBC, flags=F.FLAG_STREAM)
    with pytest.raises(F.FrameError, match="smaller than its"):
        F.peek_header(buf)
    # well-formed stream frame parses, flag surfaced on the header
    buf = F.pack_frame("x", b"", pay, F.CodeKind.PYBC, flags=F.FLAG_STREAM)
    assert F.peek_header(buf).is_stream


def test_stream_desc_roundtrip_and_validation():
    d = F.StreamDesc(total_len=1000, n_chunks=4, chunk_bytes=256, window=2,
                     codec=WC.RLE, sflags=F.SFLAG_EXEC_ON_ARRIVAL,
                     cell=256 + F.CHUNK_OVERHEAD, nonce=0xDEAD)
    buf = bytearray(F.stream_payload_len(d.window, d.cell))
    F.pack_stream_desc(buf, 0, d)
    got = F.parse_stream_desc(buf, 0, len(buf))
    assert got == d and got.exec_on_arrival
    assert got.cell_off(0) == 0 and got.cell_off(3) == d.cell  # 3 % 2 == 1

    def bad(**kw):
        b = F.StreamDesc(**{**d.__dict__, **kw})  # type: ignore[arg-type]
        buf2 = bytearray(len(buf))
        F.pack_stream_desc(buf2, 0, b)
        with pytest.raises(F.FrameError):
            F.parse_stream_desc(buf2, 0, len(buf))

    bad(window=0)                           # geometry
    bad(cell=256)                           # cell smaller than chunk+overhead
    bad(n_chunks=5)                         # count inconsistent with total
    bad(window=3)                           # cells exceed the payload section


def test_chunk_peek_pending_vs_corrupt():
    data = bytes(range(64))
    cell = bytearray(len(data) + F.CHUNK_OVERHEAD)
    hdr, seal = F.pack_chunk_hdr(3, len(data), len(data), WC.RAW, nonce=7)
    cell[:F.CHUNK_HDR_LEN] = hdr
    cell[F.CHUNK_HDR_LEN:F.CHUNK_HDR_LEN + len(data)] = data
    # seal withheld: delivered header, data in flight -> pending, not corrupt
    assert F.peek_chunk(cell, 3, nonce=7) is None
    cell[F.CHUNK_HDR_LEN + len(data):] = seal
    assert F.peek_chunk(cell, 3, nonce=7) == (len(data), len(data), WC.RAW)
    # wrong seq or wrong stream nonce: a stale/foreign chunk is pending
    assert F.peek_chunk(cell, 4, nonce=7) is None
    assert F.peek_chunk(cell, 3, nonce=8) is None
    # raw_len above the descriptor's chunk size: corrupt
    with pytest.raises(F.FrameError, match="exceeds the"):
        F.peek_chunk(cell, 3, max_raw=len(data) - 1, nonce=7)
    # comp_len indexing out of the cell: corrupt, caught before the seal read
    big, _ = F.pack_chunk_hdr(3, len(cell), len(data), WC.RAW, nonce=7)
    cell[:F.CHUNK_HDR_LEN] = big
    with pytest.raises(F.FrameError, match="exceeds its"):
        F.peek_chunk(cell, 3, nonce=7)
    # flipped covered field with an echoing seal: the fletcher catches it
    cell[:F.CHUNK_HDR_LEN] = hdr
    cell[12] ^= 0xFF                        # codec_used, inside chk coverage
    chk = struct.unpack_from("<I", bytes(cell), 16)[0]
    struct.pack_into("<I", cell, F.CHUNK_HDR_LEN + len(data), chk)
    with pytest.raises(F.FrameError, match="fletcher mismatch"):
        F.peek_chunk(cell, 3, nonce=7)


def test_blockwise_fletcher_matches_oracle(monkeypatch):
    monkeypatch.setattr(F, "_VEC_BLOCK", 8)   # force many carried blocks
    rng = np.random.default_rng(42)
    for n in (0, 1, 2, 15, 16, 17, 127, 128, 129, 255, 1024, 4097):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert F.fletcher32(data) == F.fletcher32_py(data), n


# ---------------------------------------------------------------------------
# wire codecs


def test_codec_negotiation_and_roundtrips():
    assert WC.get_codec(None).id == WC.RAW
    assert WC.get_codec("rle").id == WC.RLE
    assert WC.get_codec(WC.QUANT8).name == "quant8"
    assert WC.get_codec(WC.get_codec("rle")).id == WC.RLE
    with pytest.raises(WC.CodecError):
        WC.get_codec("zstd")

    rle = WC.get_codec("rle")
    runs = np.repeat(np.arange(5, dtype="<u4"), 200).tobytes()
    coded = rle.encode(runs)
    assert coded is not None and len(coded) < len(runs)
    assert rle.decode(coded, len(runs)) == runs
    # incompressible / unaligned input ships raw (encode declines)
    assert rle.encode(np.arange(256, dtype="<u4").tobytes()) is None
    assert rle.encode(b"abc") is None

    q8 = WC.get_codec("quant8")
    vals = np.linspace(-1.0, 1.0, 512, dtype="<f4")
    coded = q8.encode(vals.tobytes())
    assert coded is not None and len(coded) < vals.nbytes // 3
    back = np.frombuffer(q8.decode(coded, vals.nbytes), "<f4")
    assert np.allclose(back, vals, atol=1.0 / 127.0)
    with pytest.raises(WC.CodecError):
        q8.decode(coded[:-1], vals.nbytes)


# ---------------------------------------------------------------------------
# dispatcher end to end


def test_stream_exec_on_arrival_past_window(lib_dir):
    """10 chunks through a window of 3: the pump must refill in-poll and
    the streaming-aware ifunc reduces every chunk as it lands."""
    d = _mk(lib_dir)
    h = register_ifunc(d.src_ctx, "host_aggregate")
    assert h.lib.streaming                  # IFUNC_STREAM picked up by load
    vals = np.arange(5000, dtype="<u4")
    assert d.send_stream("p", h, vals.tobytes(), chunk_bytes=2048, window=3)
    d.drain()
    peer = d.peers["p"]
    assert peer.target_args["result"] == {
        "count": 5000, "sum": int(vals.sum()), "min": 0, "max": 4999}
    assert peer.stats["streams"] == 1
    assert peer.stats["stream_chunks"] == 10
    assert peer.stats["delivered"] == 1
    assert not peer.rings[0].mailbox.streams     # rx state cleaned up
    assert not d._active_streams


def test_stream_buffered_assembly_for_plain_ifunc(lib_dir):
    """A non-streaming ifunc sees ONE assembled payload, exactly as if the
    frame had been store-and-forward."""
    d = _mk(lib_dir)
    h = register_ifunc(d.src_ctx, "rle_insert")
    assert not h.lib.streaming
    payload = bytes((3, 65, 2, 66)) * 100        # RLE pairs, 400B, 7 chunks
    assert d.send_stream("p", h, payload, chunk_bytes=64, window=2)
    d.drain()
    assert d.peers["p"].target_args["db"] == [b"AAABB" * 100]


def test_stream_autoroute_threshold(lib_dir):
    d = _mk(lib_dir)
    d.set_streaming(True, chunk_bytes=2048, window=2, threshold=1024)
    h = register_ifunc(d.src_ctx, "host_aggregate")
    big = np.arange(2000, dtype="<u4")
    d.send_ifunc("p", h, big.tobytes())
    d.drain()
    peer = d.peers["p"]
    assert peer.stats["streams"] == 1            # routed into the stream path
    assert peer.target_args["result"]["count"] == 2000
    small = np.arange(100, dtype="<u4")
    d.send_ifunc("p", h, small.tobytes())
    d.drain()
    assert peer.stats["streams"] == 1            # under threshold: plain frame
    assert peer.target_args["result"]["count"] == 100


def test_stream_autoroute_off_and_striped_excluded(lib_dir):
    # streaming off: the old oversize bypass still ships a plain singleton
    d = _mk(lib_dir)
    h = register_ifunc(d.src_ctx, "host_aggregate")
    d.send_ifunc("p", h, np.arange(3000, dtype="<u4").tobytes())
    d.drain()
    assert d.peers["p"].stats.get("streams", 0) == 0
    assert d.peers["p"].target_args["result"]["count"] == 3000
    # striped peer: never auto-routed, and send_stream refuses outright (a
    # held stream slot would wedge the strict consume rotation)
    d2 = Dispatcher(Context("src", lib_dir=lib_dir),
                    ProgressEngine(flush_threshold=64))
    d2.add_peer("s", RdmaFabric(), Context("s", lib_dir=lib_dir,
                                           link_mode="remote"),
                n_slots=4, slot_size=32 << 10, rings=2, stripe=True,
                target_args={})
    d2.set_streaming(True, threshold=1024)
    h2 = register_ifunc(d2.src_ctx, "host_aggregate")
    with pytest.raises(TransportError, match="striped"):
        d2.send_stream("s", h2, bytes(8192))
    d2.send_ifunc("s", h2, np.arange(2000, dtype="<u4").tobytes())
    d2.drain()
    assert d2.peers["s"].stats.get("streams", 0) == 0
    assert d2.peers["s"].target_args["result"]["count"] == 2000


def test_stream_codec_shrinks_wire_bytes(lib_dir):
    d = _mk(lib_dir, codec="rle")
    h = register_ifunc(d.src_ctx, "host_aggregate")
    vals = np.full(8000, 7, dtype="<u4")         # 32000B of one run
    assert d.send_stream("p", h, vals.tobytes(), chunk_bytes=4096, window=2)
    d.drain()
    peer = d.peers["p"]
    assert peer.target_args["result"] == {
        "count": 8000, "sum": 7 * 8000, "min": 7, "max": 7}
    assert peer.stats["bytes"] < vals.nbytes // 4    # chunks shipped coded


def test_stream_slim_nack_rebuilds_full_exactly_once(lib_dir):
    d = _mk(lib_dir)
    h = register_ifunc(d.src_ctx, "rle_insert")
    payload = bytes((2, 67,)) * 120              # 240B -> 4 chunks of 64
    assert d.send_stream("p", h, payload, chunk_bytes=64, window=2)
    d.drain()
    peer = d.peers["p"]
    assert peer.target_args["db"] == [b"CC" * 120]
    # evict the digest: the next stream opens SLIM, gets NACK_UNCACHED at
    # the descriptor, and must rebuild FULL from chunk 0 — delivered once
    assert peer.target_ctx.link_cache.evict(h.lib.name, h.lib.code_digest)
    assert d.send_stream("p", h, payload, chunk_bytes=64, window=2)
    d.drain()
    assert peer.target_args["db"] == [b"CC" * 120, b"CC" * 120]
    assert peer.stats["nacks"] == 1
    assert peer.stats["resent"] == 1
    assert peer.stats["streams"] == 2
    assert not d._active_streams


def test_stream_geometry_clamps_to_slot(lib_dir):
    """Asked-for chunk/window far beyond the slot: the geometry clamps (so
    the FULL-fallback prefix always fits) and the stream still delivers."""
    d = _mk(lib_dir, slot_size=8 << 10)
    h = register_ifunc(d.src_ctx, "host_aggregate")
    vals = np.arange(7500, dtype="<u4")          # 30000B through an 8KiB slot
    assert d.send_stream("p", h, vals.tobytes(),
                         chunk_bytes=1 << 20, window=64)
    d.drain()
    peer = d.peers["p"]
    assert peer.target_args["result"]["count"] == 7500
    assert peer.stats["stream_chunks"] > 4       # clamped well below 1MiB


# ---------------------------------------------------------------------------
# failure modes


def test_corrupt_chunk_rejects_only_its_stream(lib_dir):
    """A chunk whose covered header was flipped (seal still echoing) must
    reject the stream — scrubbed slot, rx state dropped — and leave the
    ring usable for the next frame."""
    src = Context("src", lib_dir=lib_dir)
    tgt = Context("tgt", lib_dir=lib_dir, link_mode="remote")
    h = register_ifunc(src, "rle_insert")
    lib = h.lib
    fab = RdmaFabric()
    mb = fab.open_mailbox(tgt, 4, 16 << 10)
    chunk, nonce = 64, 5
    cell = chunk + F.CHUNK_OVERHEAD
    desc = F.StreamDesc(2 * chunk, 2, chunk, 2, WC.RAW, 0, cell, nonce)
    slab = bytearray(16 << 10)
    flen = F.seal_frame(slab, lib.name, lib.code, lib.kind,
                        F.stream_payload_len(2, cell),
                        digest=lib.code_digest, flags=F.FLAG_STREAM)
    prefix = F.HEADER_LEN + len(lib.code)
    F.pack_stream_desc(slab, prefix, desc)
    cells = prefix + F.STREAM_DESC_LEN
    data = bytes((2, 68)) * (chunk // 2)
    hdr0, seal0 = F.pack_chunk_hdr(0, chunk, chunk, WC.RAW, nonce=nonce)
    slab[cells:cells + F.CHUNK_HDR_LEN] = hdr0
    slab[cells + F.CHUNK_HDR_LEN:cells + F.CHUNK_HDR_LEN + chunk] = data
    slab[cells + cell - 4:cells + cell] = seal0
    hdr1, seal1 = F.pack_chunk_hdr(1, chunk, chunk, WC.RAW, nonce=nonce)
    bad = bytearray(hdr1)
    bad[12] ^= 0xFF                              # covered field flipped...
    c1 = cells + cell
    slab[c1:c1 + F.CHUNK_HDR_LEN] = bad
    slab[c1 + F.CHUNK_HDR_LEN:c1 + F.CHUNK_HDR_LEN + chunk] = data
    slab[c1 + cell - 4:c1 + cell] = seal1        # ...but the seal echoes chk
    mb.slot_view(0)[:flen] = slab[:flen]

    ta = {"db": []}
    sts = []
    for _ in range(4):
        sts += [c.status for c in mb.sweep(tgt, ta)[0]]
        if Status.REJECTED in sts:
            break
    assert Status.REJECTED in sts
    assert not mb.streams                        # rx state dropped
    assert ta["db"] == []                        # nothing executed
    # the ring is intact: a plain frame in the next slot delivers fine
    msg = F.pack_frame(lib.name, lib.code, bytes((1, 69)), lib.kind,
                       digest=lib.code_digest)
    mb.slot_view(1)[:len(msg)] = msg
    assert Status.OK in [c.status for c in mb.sweep(tgt, ta)[0]]
    assert ta["db"] == [b"E"]


def _wedge(d):
    """Make the peer stop consuming: sweeps observe nothing forever."""
    for r in d.peers["p"].rings:
        r.mailbox.sweep = lambda *a, **k: ([], None)


def test_fail_inflight_resolves_half_arrived_stream(lib_dir):
    d = _mk(lib_dir)
    _wedge(d)
    replies = []
    d.reply_router = lambda corr, name, value, is_err, decoded: \
        replies.append((corr, value, is_err))
    h = register_ifunc(d.src_ctx, "host_aggregate")
    assert d.send_stream("p", h, bytes(20000), corr_id=77,
                         chunk_bytes=2048, window=2)
    assert d.fail_inflight("wedged peer") >= 1
    assert len(replies) == 1
    corr, value, is_err = replies[0]
    assert corr == 77 and is_err and isinstance(value, TransportError)
    # the pump must never touch the dead stream again; drain goes idle
    d.drain()
    assert not d._active_streams


def test_drain_deadline_fails_wedged_stream(lib_dir):
    d = _mk(lib_dir)
    _wedge(d)
    replies = []
    d.reply_router = lambda corr, name, value, is_err, decoded: \
        replies.append((corr, is_err))
    h = register_ifunc(d.src_ctx, "host_aggregate")
    assert d.send_stream("p", h, bytes(20000), corr_id=88,
                         chunk_bytes=2048, window=2)
    d.drain(deadline=0.05)
    assert replies == [(88, True)]
    assert not d._active_streams


# ---------------------------------------------------------------------------
# placement: striping-aware queue-depth pricing


def test_queue_depth_scales_with_stripe_width(lib_dir):
    src = Context("src", lib_dir=lib_dir)
    d = Dispatcher(src, ProgressEngine(flush_threshold=64))
    for name, kw in (("plain", {}), ("striped", {"rings": 2, "stripe": True})):
        d.add_peer(name, RdmaFabric(), Context(name, lib_dir=lib_dir,
                                               link_mode="remote"),
                   n_slots=4, slot_size=8 << 10, target_args={"db": []}, **kw)
    eng = PlacementEngine(None, d)
    h = register_ifunc(src, "rle_insert")
    for _ in range(4):
        for name in ("plain", "striped"):
            assert d.send(name, ifunc_msg_create(h, b"\x01A"))
    # same backlog, but the striped peer drains two rings at a time: the
    # effective depth a new task sees is halved
    assert eng.queue_depth("plain") == 4
    assert eng.queue_depth("striped") == 2.0
    # retransmits stay unscaled (the resend queue is per-peer FIFO)
    d.peers["striped"].resend.append(object())
    assert eng.queue_depth("striped") == 3.0
    d.peers["striped"].resend.clear()
    d.drain()
    assert eng.queue_depth("plain") == 0
    assert eng.queue_depth("striped") == 0.0
    # and the hop pricer consumes the scaled depth
    assert eng.hop_cost("plain", 0) == pytest.approx(
        eng.hop_cost("striped", 0))
