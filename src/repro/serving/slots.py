"""Slot manager: churny streams multiplexed onto padded per-device slots.

The paper's scenario 1 (sensor-fleet data reduction) has streams that
come and go; the fixed ``(S, T)`` fleet layer cannot admit or evict
without resharding.  :class:`SlotManager` owns a *padded* slot plane —
``capacity`` rounded up to a multiple of the device count, one masked
segmenter shard per device — and maps short-lived streams onto slots:

- **admit** binds a stream to a free slot and bumps the slot's
  *generation*.  No device work happens at admission: the masked engine
  (:func:`repro.core.jax_pla.masked_step_chunk`) rebuilds the slot's
  carry row from the stream's own first point, so a recycled slot is
  structurally incapable of leaking the previous occupant's segmenter
  state; the codec geometry is fresh too (admission resets the slot's
  row of its shard's
  :class:`~repro.core.protocol_engine.SlotPlaneEmitter`).
- **step** pushes one ``(S_pad, n)`` tick plane with per-slot valid
  lengths; the jit shape never changes with churn (empty slots ride
  along as length-0 rows with ε = :data:`INACTIVE_EPS`).
- **evict** force-closes the slot's trailing run on device and flushes
  the slot's row of the wire emitter; the returned bytes are
  bit-identical to the
  offline :func:`~repro.core.protocol_engine.encode_batch` of the
  stream's own data (pinned in tests/test_serving.py).

Wire framing is per-stream and stream-local (position 0 = the stream's
first point), so slot placement and tick phasing leave no trace in the
bytes.  Each shard packs its rows' bytes in one
:class:`~repro.core.protocol_engine.SlotPlaneEmitter` call a tick.

``admit``, ``evict``, each ε-plane upload and the three phases of
``step`` (dispatch, fetch, emit: one span per shard, none per slot) open
``jax.profiler.TraceAnnotation`` spans named ``repro.slots.*``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.core import jax_pla
from repro.core.evaluate import METHOD_KNOT_KINDS
from repro.core.protocol_engine import SlotPlaneEmitter

__all__ = ["INACTIVE_EPS", "FleetFull", "Slot", "EvictReport",
           "SlotManager"]

# ε mask for empty slots.  Masked rows never step (their tick lengths
# are 0), so the value is never read by the math — it exists so a slot
# dump is self-describing and so a hypothetical stray step could never
# emit a break.  Largest finite f32 below the engine's _BIG sentinel.
INACTIVE_EPS = 3.0e38


class FleetFull(RuntimeError):
    """Admission refused: every slot is occupied."""


@dataclasses.dataclass
class Slot:
    """Host bookkeeping for one padded slot."""

    index: int                          # global row in the slot plane
    stream_id: Optional[str] = None     # None = free
    generation: int = 0                 # bumped at every admission
    nbytes: int = 0                     # wire bytes emitted since admission

    @property
    def live(self) -> bool:
        return self.stream_id is not None


@dataclasses.dataclass
class EvictReport:
    """Outcome of closing a stream: identity tags plus the tail bytes."""

    stream_id: str
    slot: int
    generation: int
    points: int
    nbytes: int           # total wire bytes over the stream's lifetime
    tail: bytes           # bytes produced by the close itself
    # Blobs emitted by the drain ticks ServeLoop.evict runs before the
    # close — (stream_id, generation, blob) tuples, possibly for *other*
    # streams whose queues drained alongside.  Empty for a bare
    # SlotManager.evict (no queues to drain at this layer).
    wire: List[Tuple[str, int, bytes]] = dataclasses.field(
        default_factory=list)


class SlotManager:
    """Padded per-device slot plane over the masked streaming engine.

    ``capacity`` is rounded up to a multiple of ``len(devices)`` (the
    padded-slot answer to ``_check_shards``: quiet rows are cheap, so the
    plane always shards evenly).  Deferred methods are rejected by
    :func:`~repro.core.jax_pla.masked_init_state`.
    """

    def __init__(self, method: str = "linear",
                 protocol: str = "singlestream", *,
                 capacity: int = 8,
                 devices: Optional[Sequence] = None,
                 eps0: float = 1.0, max_run: int = 256,
                 window: Optional[int] = None,
                 knot_kind: Optional[str] = None,
                 burst_cap: int = 127, dtype=jnp.float32, store=None):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        if store is not None and store.protocol != protocol:
            raise ValueError(f"store speaks {store.protocol!r}, "
                             f"slots emit {protocol!r}")
        self.method = method
        self.protocol = protocol
        self.knot_kind = knot_kind or METHOD_KNOT_KINDS.get(method,
                                                            "disjoint")
        self.max_run = max_run
        self.burst_cap = burst_cap
        self.eps0 = float(eps0)
        self.dtype = dtype
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        d = len(self.devices)
        self.rows_per_shard = -(-capacity // d)
        self.capacity = self.rows_per_shard * d          # padded
        self._eps = np.full((self.capacity,), INACTIVE_EPS, np.float32)
        self._states = []
        for dev in self.devices:
            st = jax_pla.masked_init_state(
                method, self.rows_per_shard,
                self._eps[:self.rows_per_shard], max_run=max_run,
                window=window, dtype=dtype)
            moved = jax.device_put(
                (st.carry, st.started, st.pos, st.eps), dev)
            self._states.append(dataclasses.replace(
                st, carry=moved[0], started=moved[1], pos=moved[2],
                eps=moved[3]))
        # One wire emitter a shard; a slot's points since admission are
        # its row's clock there.
        self._emitters = [
            SlotPlaneEmitter(protocol, self.rows_per_shard, max_run=max_run,
                             knot_kind=self.knot_kind, burst_cap=burst_cap)
            for _ in self.devices]
        self.slots: List[Slot] = [Slot(index=i)
                                  for i in range(self.capacity)]
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._by_stream: Dict[str, int] = {}
        self.total_points = 0
        self.total_bytes = 0
        # Optional archive: every blob a slot emits is appended under
        # the admission-unique key (stream_id, slot, generation), and
        # the key is closed at evict — so the store's copy of a churny
        # stream equals an offline encode of that stream's own data.
        self.store = store

    # -- admission / eviction ----------------------------------------------

    def admit(self, stream_id: str, eps: Optional[float] = None) -> Slot:
        """Bind ``stream_id`` to a free slot (LIFO — slots recycle hot)."""
        with span("repro.slots.admit"):
            return self._admit(stream_id, eps)

    def _admit(self, stream_id: str, eps: Optional[float]) -> Slot:
        if stream_id in self._by_stream:
            raise ValueError(f"stream {stream_id!r} is already admitted")
        if not self._free:
            raise FleetFull(
                f"all {self.capacity} slots occupied; evict a stream or "
                f"grow the plane")
        i = self._free.pop()
        slot = self.slots[i]
        slot.stream_id = stream_id
        slot.generation += 1
        slot.nbytes = 0
        d, r = divmod(i, self.rows_per_shard)
        self._emitters[d].reset_rows(self._row_mask(r))
        self._by_stream[stream_id] = i
        self._set_row_eps(i, self.eps0 if eps is None else float(eps))
        if self.store is not None:
            self.store.add_stream(self._store_key(slot),
                                  eps=float(self._eps[i]))
        return slot

    @staticmethod
    def _store_key(slot: Slot) -> Tuple[str, int, int]:
        """Archive key for one admission (unique: generation is a
        monotone per-slot counter, so slot+generation never repeats)."""
        return (slot.stream_id, slot.index, slot.generation)

    def _archive(self, slot: Slot, parts) -> None:
        key = self._store_key(slot)
        for p in parts:
            if self._blob(p):
                self.store.append_stream(key, p,
                                         eps=float(self._eps[slot.index]))

    def evict(self, stream_id: str) -> EvictReport:
        """Close the stream: flush its carry row and drain its emitter."""
        with span("repro.slots.evict"):
            return self._evict(stream_id)

    def _evict(self, stream_id: str) -> EvictReport:
        i = self._by_stream.pop(stream_id, None)
        if i is None:
            raise KeyError(f"stream {stream_id!r} is not admitted")
        slot = self.slots[i]
        d, r = divmod(i, self.rows_per_shard)
        mask = self._row_mask(r)
        self._states[d], final = jax_pla.masked_flush_rows(self._states[d],
                                                           mask)
        em = self._emitters[d]
        points = int(em.points[r])
        tail = b""
        if points > 0:
            # The forced break at the row's last point, as one more event
            # column of the shard; then the row's closing bytes.
            final = jax_pla.MaskedEvents(*(np.asarray(x)[:, None]
                                           for x in final))
            assert bool(final.ev[r, 0])
            part = em.step_chunk(final)[r]
            closing = em.flush_rows(mask)[0]
            tail = self._blob(part) + self._blob(closing)
            if self.store is not None:
                self._archive(slot, [part, closing])
            slot.nbytes += len(tail)
            self.total_bytes += len(tail)
        if self.store is not None:
            self.store.close([self._store_key(slot)])
        rep = EvictReport(stream_id=stream_id, slot=i,
                          generation=slot.generation, points=points,
                          nbytes=slot.nbytes, tail=tail)
        slot.stream_id = None
        self._set_row_eps(i, INACTIVE_EPS)
        self._free.append(i)
        return rep

    def _row_mask(self, r: int) -> np.ndarray:
        """A shard's ``(rows_per_shard,)`` mask selecting row ``r``."""
        mask = np.zeros((self.rows_per_shard,), bool)
        mask[r] = True
        return mask

    # -- ε plane -----------------------------------------------------------

    @property
    def eps(self) -> np.ndarray:
        """Current per-slot ε plane (inactive rows = INACTIVE_EPS)."""
        return self._eps.copy()

    def live_mask(self) -> np.ndarray:
        return np.asarray([s.live for s in self.slots], bool)

    def _set_row_eps(self, i: int, value: float) -> None:
        self._eps[i] = value
        d, r = divmod(i, self.rows_per_shard)
        self._push_shard_eps(d)

    def set_eps(self, eps) -> None:
        """Retune the live rows' ε (traced swap — no recompilation).

        ``eps`` is a ``(capacity,)`` vector; entries of free slots are
        ignored and forced back to :data:`INACTIVE_EPS`."""
        eps = np.asarray(eps, np.float32)
        if eps.shape != (self.capacity,):
            raise ValueError(f"eps must be ({self.capacity},); "
                             f"got {eps.shape}")
        live = self.live_mask()
        self._eps = np.where(live, eps, INACTIVE_EPS).astype(np.float32)
        for d in range(len(self.devices)):
            self._push_shard_eps(d)

    def _push_shard_eps(self, d: int) -> None:
        lo = d * self.rows_per_shard
        with span("repro.slots.set_eps"):
            row = jax.device_put(
                jnp.asarray(self._eps[lo:lo + self.rows_per_shard]),
                self.devices[d])
            self._states[d] = jax_pla.masked_set_eps(self._states[d], row)

    # -- tick stepping -------------------------------------------------------

    def step(self, plane, lengths) -> List[Tuple[str, int, bytes]]:
        """Consume one ``(capacity, n)`` tick plane.

        ``lengths[i]`` valid points for slot ``i`` (0 for free slots).
        Returns ``(stream_id, generation, wire_bytes)`` per slot that
        produced bytes this tick.  Shard launches are all dispatched
        before any host packing blocks on their results."""
        plane = np.asarray(plane, np.float32)
        lengths = np.asarray(lengths, np.int64)
        if plane.ndim != 2 or plane.shape[0] != self.capacity:
            raise ValueError(f"plane must be ({self.capacity}, n); "
                             f"got {plane.shape}")
        if lengths.shape != (self.capacity,):
            raise ValueError(f"lengths must be ({self.capacity},)")
        free = ~self.live_mask()
        if (lengths[free] > 0).any():
            raise ValueError("data offered to a free slot")
        R = self.rows_per_shard
        outs: Dict[int, jax_pla.MaskedEvents] = {}
        for d, dev in enumerate(self.devices):
            rows = slice(d * R, (d + 1) * R)
            if lengths[rows].max(initial=0) == 0:
                continue
            with span("repro.slots.dispatch"):
                shard_y = jax.device_put(jnp.asarray(plane[rows]), dev)
                self._states[d], outs[d] = jax_pla.masked_step_chunk(
                    self._states[d], shard_y, lengths[rows])
        wire: List[Tuple[str, int, bytes]] = []
        for d, out in outs.items():
            # Where the host waits for the shard's step.
            with span("repro.slots.fetch"):
                out = jax_pla.MaskedEvents(*(np.asarray(x) for x in out))
            rows = slice(d * R, (d + 1) * R)
            hit = np.flatnonzero(out.ev.any(axis=1))
            with span("repro.slots.emit", rows=int(hit.size),
                      events=int(np.count_nonzero(out.ev))):
                parts = self._emitters[d].step_chunk(
                    out, (plane[rows], lengths[rows]))
                # Only a row with events has bytes.
                for r in hit.tolist():
                    blob = self._blob(parts[r])
                    if not blob:
                        continue
                    slot = self.slots[d * R + r]
                    if self.store is not None:
                        self._archive(slot, [parts[r]])
                    slot.nbytes += len(blob)
                    self.total_bytes += len(blob)
                    wire.append((slot.stream_id, slot.generation, blob))
        self.total_points += int(lengths.sum())
        return wire

    @staticmethod
    def _blob(part) -> bytes:
        """Flatten a per-stream emitter return (bytes, or a pair of
        byte strings for the twostreams protocol) into one blob."""
        return part if isinstance(part, bytes) else b"".join(part)
