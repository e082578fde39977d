"""Fleet ingest: a client pushes column blocks of every stream through
``repro.sharding.fleet.FleetStream`` back to back (closed loop).

Set-up makes ``DATA_BLOCKS`` consecutive blocks of the walk on the
device, copies them to the host, builds the fleet, and pushes
``WARM_PUSHES`` blocks so that every program the window runs is
compiled.  The blocks are then played forward and back in turn (0, 1,
.., D-1, D-1 reversed, .., 0 reversed, 0, ..), so every stream stays one
continuous walk however many pushes a window holds: a reversed clamped
N(0, 1) walk is such a walk too, and each turn repeats one sample.  The
window pushes until ``seconds`` have passed, and records each push's
start, end and points.  After the window the fleet is finished and each
sampled stream's bytes, from its first push to its close, are its
answer: ``SAMPLE_STREAMS`` streams drawn from the seed, an equal number
from each chip's shard.
"""

from __future__ import annotations

import time

import numpy as np

from bench.core import tsbs
from bench.core.check import Sample

DATA_BLOCKS = 4       # distinct blocks of the walk, played forward and back
WARM_PUSHES = 2       # pushes in set-up: every program the window runs
SAMPLE_STREAMS = 64   # streams whose answers are judged
MAX_RUN = 256         # the segment counter's cap (singlestream's, 8 bits)


def _join(parts):
    if parts and isinstance(parts[0], tuple):
        return tuple(b"".join(p) for p in zip(*parts))
    return b"".join(parts)


class System:
    def __init__(self, cell, seed: int, devices):
        self.cell = cell
        self.seed = int(seed)
        self.devices = devices
        c, tr = cell.config, cell.traffic
        self.n_streams = int(c["hosts"]) * int(c["metrics_per_host"])
        self.width = int(tr["push_width"])
        self.eps = float(c["eps"])
        self.method, self.protocol = tr["method"], tr["protocol"]
        self.t0, self.dt = float(c["t0"]), float(c["dt"])
        # The fleet splits its rows into one contiguous shard per chip;
        # each shard gives SAMPLE_STREAMS // chips sampled rows, so that a
        # fault confined to one shard shows.  On one chip this is one draw
        # over all rows.
        rng = np.random.default_rng(self.seed)
        per = self.n_streams // len(devices)
        k = min(SAMPLE_STREAMS // len(devices), per)
        self.rows = np.concatenate([
            d * per + np.sort(rng.choice(per, k, replace=False))
            for d in range(len(devices))])
        self.order = []             # index into blocks of each push
        self.kept = [[] for _ in self.rows]

    def setup(self, seconds: float) -> None:
        from repro.sharding.fleet import FleetStream
        forward = tsbs.walk_blocks(
            self.seed, self.n_streams, self.width, DATA_BLOCKS,
            self.cell.config["walk"], self.devices[0])
        self.blocks = forward + [np.ascontiguousarray(b[:, ::-1])
                                 for b in reversed(forward)]
        self.fleet = FleetStream(
            self.method, self.protocol, self.n_streams, self.eps,
            devices=self.devices, max_run=MAX_RUN, t0=self.t0, dt=self.dt)
        for _ in range(WARM_PUSHES):
            self._push()

    def _push(self) -> int:
        k = len(self.order) % len(self.blocks)
        out = self.fleet.push(self.blocks[k])
        self.order.append(k)
        for i, r in enumerate(self.rows):
            self.kept[i].append(out[r])
        return len(out)

    def window(self, seconds: float) -> dict:
        import jax
        pushes, failed = [], 0
        start = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.fleet.push"):
                n = self._push()
            end = time.perf_counter()
            failed += n != self.n_streams
            pushes.append((t, end, self.n_streams * self.width))
            if end - start >= seconds:
                break
        return {"attempted": len(pushes), "failed": failed,
                "pushes": pushes, "push_points": self.n_streams * self.width,
                "n_streams": self.n_streams, "push_width": self.width}

    def answers(self) -> list:
        """Finish the fleet; the sampled streams with their whole bytes."""
        fin = self.fleet.finish()
        del self.fleet
        n = len(self.order) * self.width
        ts = self.t0 + self.dt * np.arange(n, dtype=np.float64)
        out = []
        for i, r in enumerate(self.rows):
            ys = np.concatenate([self.blocks[k][r] for k in self.order])
            out.append(Sample(int(r), ts, ys,
                              _join(self.kept[i] + [fin[r]])))
        return out

    def close(self) -> None:
        self.blocks = None
