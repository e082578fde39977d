"""Churny serving: an open-loop client feeds ``repro.serving.ServeLoop``
over a ``SlotManager`` plane.

Every stream gets its samples on a fixed schedule, ``rate`` samples per
second from its admission on, at a phase of its own drawn from the seed.
Streams leave and new ones arrive at ``churn_per_s``, at times fixed by
the schedule; which live stream leaves is drawn from the seed.  The
loop, as a server would run it: admit and evict what is due, offer every
live stream the samples that have come due, tick.  A slow tick does not
slow the schedule: samples wait in the queues, and their latency counts
the wait.

A sample's latency runs from the time it was due to the return of the
tick (or of the evict that drained it) that consumed it; a sample still
due when the window closes counts at its age then.  Set-up makes the
data on the device, fills every slot and runs one tick, which compiles
or loads every program the tick runs; the schedule's clock starts after
that tick.  The warm-up then runs the loop until, after the first churn
event, ``WARM_MIN_TICKS`` ticks in a row leave every queue empty (the
steady state below the knee), or for ``WARM_MAX_S`` at most, so the
window starts with every program built and no backlog from set-up.  After the window no more samples are
offered; each sampled stream still live is evicted, and the bytes it was
delivered over its life are its answer.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench.core import tsbs
from bench.core.check import Sample

BLOCK = 1024          # samples per generated block, one compiled shape
QUEUE_CAP = 1024      # samples a slot's queue holds (ServeLoop, policy block)
WARM_MIN_TICKS = 3    # clean ticks in a row that end the warm-up
WARM_MAX_S = 60.0     # the longest warm-up: beyond it the window starts
SAMPLE_STREAMS = 64   # streams whose answers are judged
MAX_RUN = 256         # the segment counter's cap (singlestream's, 8 bits)


class System:
    def __init__(self, cell, seed: int, devices):
        self.cell = cell
        self.seed = int(seed)
        self.devices = devices
        c, tr = cell.config, cell.traffic
        self.capacity = int(c["slots"])
        self.eps = float(c["eps"])
        self.method, self.protocol = tr["method"], tr["protocol"]
        self.rate = float(tr["rate_per_stream"])
        self.churn = float(tr["churn_per_s"])
        self.tick_width = int(tr["tick_width"])
        self.rng = np.random.default_rng(self.seed)

    # -- schedule -----------------------------------------------------------

    def _plan(self, seconds: float) -> None:
        horizon = WARM_MAX_S + seconds
        n_churn = int(math.floor(horizon * self.churn))
        self.n_total = self.capacity + n_churn
        self.length = int(math.ceil(horizon * self.rate)) + 1
        self.phase = self.rng.uniform(0.0, 1.0 / self.rate, self.n_total)
        self.admit_at = np.zeros(self.n_total)
        self.admit_at[self.capacity:] = (np.arange(n_churn) + 1) / self.churn
        # The k-th churn event evicts a uniformly drawn live slot.
        self.victim_slot = self.rng.integers(0, self.capacity, n_churn)

    def setup(self, seconds: float) -> None:
        from repro.serving import ServeLoop, SlotManager
        self._plan(seconds)
        n_blocks = -(-self.length // BLOCK)
        blocks = tsbs.walk_blocks(self.seed, self.n_total, BLOCK, n_blocks,
                                  self.cell.config["walk"], self.devices[0])
        self.data = np.concatenate(blocks, axis=1)[:, :self.length]
        del blocks
        self.mgr = SlotManager(self.method, self.protocol,
                               capacity=self.capacity, devices=self.devices,
                               eps0=self.eps, max_run=MAX_RUN)
        self.loop = ServeLoop(self.mgr, tick_width=self.tick_width,
                              queue_cap=QUEUE_CAP, policy="block")
        self.stream_of = np.full(self.capacity, -1, np.int64)
        self.offered = np.zeros(self.n_total, np.int64)
        self.consumed = np.zeros(self.n_total, np.int64)
        self.delivered = {}
        self.next_churn = 0
        self.log = []          # (t_return, streams, c0, c1) per consume
        self.base = time.perf_counter()
        for s in range(self.capacity):
            self._admit(s)
        self._step()
        # The schedule starts once the first tick has built its programs:
        # a compile or a cache load of any length leaves no backlog.
        self.base = time.perf_counter()
        ticks = clean = 0
        while self._now() < WARM_MAX_S and clean < WARM_MIN_TICKS:
            churned = self.next_churn > 0
            self._step()
            ticks += 1
            # A clean tick follows the first churn event (whose evict and
            # admit build their programs) and leaves every queue empty.
            clean = clean + 1 if churned and not self.loop.backlog().any() \
                else 0
        self.warm = {"warm_ticks": ticks, "warm_s": self._now(),
                     "backlog_start": self._backlog(self._now())}

    # -- loop ---------------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self.base

    def _admit(self, s: int) -> None:
        slot = self.loop.admit(str(s), eps=self.eps)
        self.stream_of[slot.index] = s
        self.delivered[s] = []

    def _collect(self, wire) -> None:
        for sid, _, blob in wire:
            self.delivered[int(sid)].append(blob)

    def _account(self) -> None:
        """Record what the last tick or evict consumed, per stream."""
        depth = self.loop.backlog()
        live = self.stream_of >= 0
        streams = self.stream_of[live]
        now = self._now()
        done = self.offered[streams] - depth[live]
        moved = done > self.consumed[streams]
        if moved.any():
            s = streams[moved]
            self.log.append((now, s, self.consumed[s].copy(), done[moved]))
            self.consumed[s] = done[moved]

    def _evict(self, s: int) -> None:
        import jax
        queued = self.offered[s] > self.consumed[s]
        with jax.profiler.TraceAnnotation("bench.serve.evict"):
            rep = self.loop.evict(str(s))
        self._collect(rep.wire)
        self.delivered[s].append(rep.tail)
        if queued:             # the evict ran ticks to drain the queue
            self._account()
        self.consumed[s] = self.offered[s]
        self.stream_of[rep.slot] = -1

    def _churn(self, now: float) -> None:
        import jax
        while (self.capacity + self.next_churn < self.n_total
               and self.admit_at[self.capacity + self.next_churn] <= now):
            new = self.capacity + self.next_churn
            slot = int(self.victim_slot[self.next_churn])
            self._evict(int(self.stream_of[slot]))
            with jax.profiler.TraceAnnotation("bench.serve.admit"):
                self._admit(new)
            self.next_churn += 1

    def _due(self, streams: np.ndarray, now: float):
        """Each stream's first due time, and its samples due by ``now``."""
        start = self.admit_at[streams] + self.phase[streams]
        due = np.where(now >= start, np.floor((now - start) * self.rate) + 1,
                       0)
        return start, np.minimum(due.astype(np.int64), self.length)

    def _offer(self, now: float) -> float:
        """Offer every live stream the samples due by ``now``; returns how
        late the oldest of them was offered."""
        import jax
        streams = self.stream_of[self.stream_of >= 0]
        start, due = self._due(streams, now)
        new = due > self.offered[streams]
        if not new.any():
            return 0.0
        lag = now - float(np.min(start[new]
                                 + self.offered[streams[new]] / self.rate))
        with jax.profiler.TraceAnnotation("bench.serve.offer"):
            for s, d in zip(streams[new].tolist(), due[new].tolist()):
                o = self.offered[s]
                self.offered[s] = o + self.loop.offer(str(s),
                                                      self.data[s, o:d])
        return lag

    def _step(self) -> float:
        import jax
        now = self._now()
        self._churn(now)
        lag = self._offer(now)
        with jax.profiler.TraceAnnotation("bench.serve.tick"):
            rep = self.loop.tick()
        self._collect(rep.wire)
        self._account()
        return lag

    def _run_until(self, t_end: float) -> tuple:
        ticks, lag = 0, 0.0
        while self._now() < t_end:
            lag = max(lag, self._step())
            ticks += 1
        return ticks, lag

    def window(self, seconds: float) -> dict:
        start = self._now()
        n_log = len(self.log)
        ticks, lag = self._run_until(start + seconds)
        end = self._now()
        waiting = self._waiting(end)
        lat = np.concatenate([self._latencies(self.log[n_log:]), waiting])
        return {"attempted": int(lat.size), "failed": 0, "ticks": ticks,
                "window": (start, end), "latencies_s": lat,
                **self.warm, "backlog_end": int(waiting.size),
                "offer_lag_max_s": lag}

    def _backlog(self, now: float) -> int:
        """Samples due by ``now`` and not yet consumed."""
        streams = self.stream_of[self.stream_of >= 0]
        return int((self._due(streams, now)[1]
                    - self.consumed[streams]).sum())

    def _waiting(self, now: float) -> np.ndarray:
        """The age at ``now`` of every sample due by then and not yet
        consumed."""
        streams = self.stream_of[self.stream_of >= 0]
        c0 = self.consumed[streams]
        c1 = np.maximum(self._due(streams, now)[1], c0)
        return now - self._due_times(streams, c0, c1)

    def _latencies(self, log) -> np.ndarray:
        """Every consumed sample's latency: its consume's return time less
        the time the sample was due."""
        parts = [t_ret - self._due_times(s, c0, c1)
                 for t_ret, s, c0, c1 in log]
        return np.concatenate(parts) if parts else np.zeros(0)

    def _due_times(self, s, c0, c1) -> np.ndarray:
        """The due times of samples ``c0[i]`` up to ``c1[i]`` of each
        stream ``s[i]``."""
        k = c1 - c0
        rows = np.repeat(np.arange(len(s)), k)
        idx = np.arange(k.sum()) - np.repeat(np.cumsum(k) - k, k) + c0[rows]
        return self.admit_at[s][rows] + self.phase[s][rows] + idx / self.rate

    # -- answers ------------------------------------------------------------

    def answers(self) -> list:
        """Evict the sampled streams still live; their bytes and samples."""
        admitted = self.capacity + self.next_churn
        n_sample = min(SAMPLE_STREAMS, admitted)
        pick = set(self.rng.choice(admitted, n_sample - 1,
                                   replace=False).tolist())
        pick.add(int(np.argmax(self.offered[:admitted])))
        live = set(self.stream_of.tolist())
        out = []
        for s in sorted(pick):
            if s in live:
                self._evict(s)
            n = int(self.offered[s])
            if n == 0:
                continue
            ts = np.arange(n, dtype=np.float64)
            out.append(Sample(s, ts, self.data[s, :n],
                              b"".join(self.delivered[s])))
        return out

    def close(self) -> None:
        self.loop = self.mgr = None
        self.data = None
