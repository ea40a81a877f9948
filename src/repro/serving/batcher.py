"""True continuous batching: a fixed-slot decode engine where every slot
tracks its own position.

The wave batcher this replaces (PR 4's ``launch/serve.py``) shared one
``slot_pos`` vector across the batch, so all sequences had to advance in
lockstep and a new admission stalled until the wave drained.  Here the
cache uses the per-slot layout (``models.transformer.init_cache(...,
per_slot=True)``): ``attention_decode`` takes a ``[B]`` position vector,
each row writes its own ring slot and masks against its own validity row,
and sequences join/leave mid-wave — the admission path is a row splice,
never a barrier.

The splice is one jitted program that takes the cache donated and writes
the new sequence's row of every entry in place: KV rings with their
``slot_pos``, and recurrent ``state``/``conv`` rows (Mamba-2, RG-LRU),
which have no positions and are overwritten whole when a slot is reused.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import Obs
from repro.train import serve as SRV


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: list = field(default_factory=list)


def synth_slot_pos(pos0: int, width: int) -> np.ndarray:
    """Reconstruct a prefilled sequence's ring occupancy from its length:
    positions 0..pos0-1 occupy slots 0..pos0-1, the rest are empty (-1).
    This is what the KV slab format elides from the wire (kv.py)."""
    row = np.full((width,), -1, np.int32)
    row[:pos0] = np.arange(pos0, dtype=np.int32)
    return row


class ContinuousBatcher:
    """B decode slots over one per-slot cache; sequences admitted and
    retired independently per tick."""

    def __init__(self, cfg: ModelConfig, params, batch_slots: int,
                 cache_len: int, *, obs: Obs | None = None,
                 name: str = "decode"):
        self.cfg, self.params = cfg, params
        self.B, self.W = batch_slots, cache_len
        self.name = name
        self.cache = T.init_cache(cfg, batch_slots, cache_len, per_slot=True)
        self.pos = np.zeros(batch_slots, np.int32)      # per-slot next position
        self.tokens = np.zeros((batch_slots, 1), np.int32)
        self.active: dict[int, Request] = {}            # slot -> request
        self._decode = SRV.jit_decode_step(cfg, donate=True)
        self._one = T.cache_shapes(cfg, 1, cache_len, per_slot=True)
        full = T.cache_shapes(cfg, batch_slots, cache_len, per_slot=True)
        bdims = {k: next((i for i, (a, b) in enumerate(
            zip(full[k].shape, v.shape)) if a != b), None)
            for k, v in self._one.items()}
        self._splice = jax.jit(functools.partial(_splice, bdims),
                               donate_argnums=0)
        self.state_bytes = sum(
            int(np.prod(self._one[k].shape)) * self._one[k].dtype.itemsize
            for k in T.recurrent_keys(cfg))
        self.obs = obs if obs is not None else Obs(name)
        m = self.obs.metrics
        self._state_spliced = m.counter(f"serve.{name}.state_bytes")
        self._installed = m.counter(f"serve.{name}.installed")
        self._decoded = m.counter(f"serve.{name}.decoded")
        self._finished = m.counter(f"serve.{name}.finished")

    def free_slots(self) -> list[int]:
        return [s for s in range(self.B) if s not in self.active]

    def install(self, slot: int, cache1: dict, pos0: int, first_token: int,
                req: Request) -> None:
        """Splice one prefilled sequence (a single-sequence cache at seq
        width <= W, with or without ``slot_pos`` entries — a KV slab
        arrives without them) into decode slot ``slot`` and activate it.
        A pure row write, in place: every other slot keeps decoding
        undisturbed, and the slot's recurrent rows are replaced whole."""
        if slot in self.active:
            raise ValueError(f"slot {slot} already active")
        if not (0 < pos0 <= self.W):
            raise ValueError(f"pos0 {pos0} outside cache width {self.W}")
        with self.obs.tracer.scope("repro.serve.install",
                                   state_bytes=self.state_bytes):
            src = dict(cache1)
            for k, tgt in self._one.items():
                if k not in src and k.endswith("slot_pos"):
                    base = synth_slot_pos(pos0, tgt.shape[-1])
                    src[k] = jnp.asarray(np.broadcast_to(base, tgt.shape))
            src = SRV.pad_cache_to(src, self._one)
            self.cache = self._splice(self.cache, src, jnp.int32(slot))
        self._state_spliced.inc(self.state_bytes)
        self.tokens[slot, 0] = int(first_token)
        self.pos[slot] = pos0
        self.active[slot] = req
        req.out.append(int(first_token))
        self._installed.inc()

    def tick(self) -> tuple[int, list[Request]]:
        """One decode step for all active slots.  Returns (#tokens
        emitted, finished requests) — completion surfaces HERE, off the
        decode path, never at admission time."""
        if not self.active:
            return 0, []
        tr = self.obs.tracer
        with tr.scope("repro.serve.decode_dispatch", n=len(self.active)):
            self.cache, logits = self._decode(self.params, self.cache,
                                              jnp.asarray(self.tokens),
                                              jnp.asarray(self.pos))
        with tr.scope("repro.serve.token_wait"):
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        emitted, finished = 0, []
        for slot, req in list(self.active.items()):
            tok = int(nxt[slot])
            req.out.append(tok)
            self.tokens[slot, 0] = tok
            self.pos[slot] += 1
            emitted += 1
            if len(req.out) >= req.max_new:
                del self.active[slot]
                self.pos[slot] = 0
                self.tokens[slot, 0] = 0
                finished.append(req)
        self._decoded.inc(emitted)
        self._finished.inc(len(finished))
        return emitted, finished


def _splice(bdims: dict, cache: dict, rows: dict, slot):
    """``cache`` with ``rows`` (one slot's entries) written at ``slot`` on
    each entry's batch axis; an entry without one (a batch of one) is
    replaced."""
    return {k: rows[k].astype(v.dtype) if bdims[k] is None
            else jax.lax.dynamic_update_slice_in_dim(
                v, rows[k].astype(v.dtype), slot, bdims[k])
            for k, v in cache.items()}


__all__ = ["Request", "ContinuousBatcher", "synth_slot_pos"]
