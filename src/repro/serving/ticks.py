"""Tick loop: out-of-phase arrivals batched into fixed-shape pushes.

Sensors deliver whenever they like; the device engine wants one
``(S_pad, n)`` plane per launch.  :class:`ServeLoop` sits between them:

- ``offer()`` appends a stream's new samples to its slot's *bounded*
  ingress queue and surfaces backpressure to the caller — under the
  ``"shed"`` policy the overflow suffix is dropped (and counted), under
  ``"block"`` it is refused and the caller retries later; either way the
  return value says how many points were accepted.
- ``tick()`` drains up to ``tick_width`` points per slot into one padded
  plane with per-slot valid lengths and steps the
  :class:`~repro.serving.slots.SlotManager`; empty slots ride along as
  length-0 rows, so the jit shape is identical every tick regardless of
  churn or phase.
- with a :class:`~repro.serving.budget.GlobalEpsBudget` attached, each
  tick's measured per-slot bytes/points feed one fleet-wide ε
  allocation round, pushed back into the slot plane as a traced swap.

Each chunk an ``offer()`` queues is stamped with the host clock; a tick
reports the points-weighted mean and the largest wait of what it
drained (``TickReport.wait_mean_s`` / ``wait_max_s``).  ``tick()`` and
``evict()`` open ``jax.profiler.TraceAnnotation`` spans named
``repro.serve.*`` around their phases (``docs/ARCHITECTURE.md`` lists
them); they cost about a microsecond each with the profiler off, and
none opens inside a loop over slots.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation as span

from .budget import GlobalEpsBudget
from .slots import EvictReport, Slot, SlotManager

__all__ = ["ServeLoop", "TickReport"]


class _Queue:
    """Append-only chunk list with O(1) bookkeeping, drained per tick.
    Each chunk keeps the host time it was pushed at."""

    __slots__ = ("parts", "stamps", "n")

    def __init__(self):
        self.parts: List[np.ndarray] = []
        self.stamps: List[float] = []
        self.n = 0

    def push(self, arr: np.ndarray) -> None:
        if arr.size:
            self.parts.append(arr)
            self.stamps.append(time.perf_counter())
            self.n += arr.size

    def pop(self, k: int, now: float) -> Tuple[np.ndarray, float]:
        """Up to ``k`` points, and their points-weighted mean wait in
        seconds from their push to ``now``."""
        k = min(k, self.n)
        out, got, waited = [], 0, 0.0
        while got < k:
            head = self.parts[0]
            take = min(head.size, k - got)
            out.append(head[:take])
            waited += take * (now - self.stamps[0])
            if take == head.size:
                self.parts.pop(0)
                self.stamps.pop(0)
            else:
                self.parts[0] = head[take:]
            got += take
        self.n -= got
        if not out:
            return np.zeros(0, np.float32), 0.0
        return np.concatenate(out), waited / got


@dataclasses.dataclass
class TickReport:
    """What one tick did — throughput, backpressure and budget state."""

    tick: int
    consumed: int                 # points stepped this tick
    nbytes: int                   # wire bytes emitted this tick
    live: int                     # occupied slots
    backlog: int                  # points still queued after the tick
    shed_total: int               # points dropped since construction
    eps_lo: float                 # live-row ε range after any retune
    eps_hi: float
    budget_pool: Optional[float]  # byte pool of this tick's allocation
    wait_mean_s: float            # points-weighted queue wait of `consumed`
    wait_max_s: float             # the largest per-slot mean wait
    wire: List[Tuple[str, int, bytes]]   # (stream_id, generation, blob)


class ServeLoop:
    """Admission-controlled serving front-end over a slot plane."""

    def __init__(self, slots: SlotManager, *, tick_width: int = 64,
                 queue_cap: int = 1024, policy: str = "shed",
                 budget: Optional[GlobalEpsBudget] = None,
                 retune_every: int = 1):
        if policy not in ("shed", "block"):
            raise ValueError(f"policy must be 'shed' or 'block'; "
                             f"got {policy!r}")
        if tick_width <= 0 or queue_cap <= 0:
            raise ValueError("tick_width and queue_cap must be positive")
        self.slots = slots
        self.tick_width = tick_width
        self.queue_cap = queue_cap
        self.policy = policy
        self.budget = budget
        self.retune_every = max(int(retune_every), 1)
        self._queues: Dict[int, _Queue] = {}
        self.ticks = 0
        self.shed_total = 0

    # -- admission ----------------------------------------------------------

    def admit(self, stream_id: str, eps: Optional[float] = None) -> Slot:
        slot = self.slots.admit(stream_id, eps)
        self._queues[slot.index] = _Queue()
        if self.budget is not None:
            rows = np.zeros(self.slots.capacity, bool)
            rows[slot.index] = True
            self.budget.reset_rows(rows)
        return slot

    def evict(self, stream_id: str, *, drain: bool = True) -> EvictReport:
        """Close a stream.  With ``drain`` (default) queued points are
        pushed through first, so the wire covers everything accepted:
        the blobs those drain ticks emit — for this stream *and* for any
        other stream whose queue drained alongside — come back on
        ``EvictReport.wire``, with ``tail`` holding the close bytes.
        ``drain=False`` discards the backlog."""
        i = self.slots._by_stream.get(stream_id)
        if i is None:
            raise KeyError(f"stream {stream_id!r} is not admitted")
        drained: List[Tuple[str, int, bytes]] = []
        with span("repro.serve.evict", queued=self._queues[i].n):
            if drain:
                while self._queues[i].n:
                    drained.extend(self.tick().wire)
            self._queues.pop(i, None)
            rep = self.slots.evict(stream_id)
            rep.wire = drained
            if self.budget is not None:
                rows = np.zeros(self.slots.capacity, bool)
                rows[i] = True
                self.budget.reset_rows(rows)
        return rep

    # -- ingress ------------------------------------------------------------

    def offer(self, stream_id: str, values) -> int:
        """Queue new samples; returns how many were accepted.

        ``shed`` drops the overflow suffix permanently (counted in
        ``shed_total``); ``block`` leaves it with the caller to retry
        after a tick has drained the queue."""
        i = self.slots._by_stream.get(stream_id)
        if i is None:
            raise KeyError(f"stream {stream_id!r} is not admitted")
        values = np.asarray(values, np.float32).ravel()
        q = self._queues[i]
        take = min(self.queue_cap - q.n, values.size)
        q.push(values[:take])
        if self.policy == "shed":
            self.shed_total += values.size - take
        return int(take)

    def backlog(self) -> np.ndarray:
        """Per-slot queued point counts (the lag signal)."""
        depth = np.zeros(self.slots.capacity, np.int64)
        for i, q in self._queues.items():
            depth[i] = q.n
        return depth

    # -- the tick -----------------------------------------------------------

    def tick(self) -> TickReport:
        """Drain up to ``tick_width`` points per slot and step the fleet."""
        with span("repro.serve.tick", tick=self.ticks + 1):
            return self._tick()

    def _tick(self) -> TickReport:
        cap = self.slots.capacity
        with span("repro.serve.drain"):
            plane = np.zeros((cap, self.tick_width), np.float32)
            lengths = np.zeros(cap, np.int64)
            waited, wait_max = 0.0, 0.0
            now = time.perf_counter()
            for i, q in self._queues.items():
                if q.n:
                    part, wait = q.pop(self.tick_width, now)
                    lengths[i] = part.size
                    plane[i, :part.size] = part
                    waited += wait * part.size
                    wait_max = max(wait_max, wait)
            before_bytes = {i: self.slots.slots[i].nbytes
                            for i in self._queues}
        consumed = int(lengths.sum())
        wait_mean = waited / consumed if consumed else 0.0
        with span("repro.slots.step",
                  slots_fed=int(np.count_nonzero(lengths)), points=consumed,
                  wait_mean_ms=1e3 * wait_mean, wait_max_ms=1e3 * wait_max):
            wire = self.slots.step(plane, lengths)
        self.ticks += 1
        live = self.slots.live_mask()
        pool = None
        if self.budget is not None and live.any() \
                and self.ticks % self.retune_every == 0:
            with span("repro.serve.budget"):
                tick_bytes = np.zeros(cap, np.float64)
                for i in before_bytes:
                    tick_bytes[i] = (self.slots.slots[i].nbytes
                                     - before_bytes[i])
                new_eps = self.budget.retune(self.slots.eps, tick_bytes,
                                             lengths, live)
                self.slots.set_eps(new_eps)
                pool = self.budget.last_pool
        with span("repro.serve.report"):
            eps_live = self.slots.eps[live]
            return TickReport(
                tick=self.ticks, consumed=consumed,
                nbytes=sum(len(b) for _, _, b in wire),
                live=int(live.sum()), backlog=int(self.backlog().sum()),
                shed_total=self.shed_total,
                eps_lo=float(eps_live.min()) if eps_live.size
                else float("nan"),
                eps_hi=float(eps_live.max()) if eps_live.size
                else float("nan"),
                budget_pool=pool, wait_mean_s=wait_mean,
                wait_max_s=wait_max, wire=wire)
