#!/usr/bin/env python3
"""Run the PLA system's main path on a TPU and check what comes out.

    python chip_smoke.py [--seed 0] [--chips 4]

With one chip (the default) three phases go through the entry points a
user calls, on data made from ``--seed`` in the shape of the TSBS devops
``cpu-only`` use case (github.com/timescale/tsbs: 10 CPU metrics per
host, each a clamped random walk in [0, 100], one sample every 10 s):

- **fleet**: 4,096 hosts x 10 metrics = 40,960 streams, four windows of
  1,024 samples through :class:`repro.sharding.fleet.FleetStream`, for
  all 13 Table-2 combinations.  Every stream's pushed + finished bytes
  must equal ``encode_batch`` of the offline segmentation; on 64 sampled
  streams per combination they must also decode, through the sequential
  ``core/protocols.py`` codecs, to within ε of the data, and equal those
  codecs' own encoding wherever they model the knot kind.
- **serve**: :class:`repro.serving.ServeLoop` over 4,096 live slots,
  8 ticks of 256 samples, 10% churn per tick.  The bytes delivered for
  every evicted stream must equal the offline encode of its own data.
- **store**: a :class:`repro.store.SegmentStore` fed by a 4,096-stream
  ``FleetStream``, then 16 windowed avg/min/max/corr queries.  Every
  answer must lie within its own error bound of ``store.scan``
  decode-then-numpy.

``--chips 4`` runs only the stream-sharded fleet path: ``FleetStream``
over four devices and ``fleet_point_metrics`` on a four-device
``fleet_mesh`` (shard_map with psum/pmean), compared with the one-device
results and ``encode_batch``.

Each phase prints its wall time, compile time, persistent-cache hits,
points and wire bytes.  These are bring-up numbers, not benchmark
metrics.  The last line is one JSON object naming the device.  The
script exits non-zero, without that line, when JAX finds no TPU, when a
kernel would run in interpret mode, or when any check fails.  It runs in
one process and keeps JAX's compile cache in ``JAX_COMPILATION_CACHE_DIR``
or, when that is unset, in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

T0 = 1451606400.0          # TSBS default start, 2016-01-01T00:00:00Z
DT = 10.0                  # seconds between samples
EPS = 1.0                  # one percentage point of CPU usage
HOSTS, METRICS = 4096, 10
WINDOW, N_WINDOWS = 1024, 4
N_SEQ = 64                 # streams per combination checked sequentially
SERVE_SLOTS, TICK_WIDTH, N_TICKS, CHURN = 4096, 256, 8, 0.10
STORE_STREAMS, N_QUERIES, QUERY_STREAMS = 4096, 16, 64
MESH_COMBOS = ("A1", "Sw", "C")     # two-stream, joint-knot, deferred
MESH_STREAMS = 8192        # fleet_point_metrics keeps ten (S, T) planes


class CheckFailed(Exception):
    """A result disagreed with its reference."""


def check(ok, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


class PhaseClock:
    """Wall time, and the compiles and persistent-cache hits that
    ``bench/core/compile_meter.py``'s counter saw, over each phase."""

    def __init__(self, meter):
        self.meter = meter

    def mark(self):
        return time.perf_counter(), self.meter.snapshot()

    def line(self, name: str, mark, points: int, wire_bytes: int,
             extra: str = "") -> str:
        t, before = mark
        now = self.meter.snapshot()
        return (f"[{name}] wall_s={time.perf_counter() - t:.3f} "
                f"compile_s={now['compile_s'] - before['compile_s']:.3f} "
                f"cache_hits={now['cache_hits'] - before['cache_hits']} "
                f"points={points} "
                f"wire_bytes={wire_bytes}{' ' + extra if extra else ''}")


def tsbs_cpu(rng, n_streams: int, n_points: int) -> np.ndarray:
    """``(n_streams, n_points)`` float32 clamped random walks: each starts
    uniform in [0, 100) and steps by N(0, 1), clamped to [0, 100]."""
    v = rng.uniform(0.0, 100.0, n_streams)
    out = np.empty((n_points, n_streams), np.float32)
    for t in range(n_points):
        v = np.clip(v + rng.standard_normal(n_streams), 0.0, 100.0)
        out[t] = v
    return np.ascontiguousarray(out.T)


def wire_len(blob) -> int:
    return len(blob[0]) + len(blob[1]) if isinstance(blob, tuple) \
        else len(blob)


def run_fleet_stream(fs, y) -> list:
    """Push ``y`` through ``fs`` in windows; each stream's joined bytes."""
    got = [(b"", b"") if fs.protocol == "twostreams" else b""] \
        * fs.n_streams
    parts = [fs.push(y[:, w * WINDOW:(w + 1) * WINDOW])
             for w in range(y.shape[1] // WINDOW)] + [fs.finish()]
    for part in parts:
        if fs.protocol == "twostreams":
            got = [(g[0] + p[0], g[1] + p[1]) for g, p in zip(got, part)]
        else:
            got = [g + p for g, p in zip(got, part)]
    return got


def offline_segmentation(method: str, y, max_run: int, device):
    """The one-shot kernel segmentation, on ``device``, as host arrays."""
    import jax
    from repro.kernels.ops import KERNEL_SEGMENTERS
    seg = KERNEL_SEGMENTERS[method](jax.device_put(y, device), EPS,
                                    max_run=max_run)
    return type(seg)(*(np.asarray(x) for x in seg))


def check_sequential(label, method, protocol, knot_kind, cap, seg, y, rows,
                     got) -> int:
    """Hold sampled streams to the sequential ``core/protocols.py`` codecs
    and compare with the sequential ``core/methods.py`` segmentation.

    Returns how many sampled streams have the same break positions as
    ``core/methods.py``, which works in float64 where the chip works in
    float32, so the count is reported rather than required.
    """
    from repro.core.jax_pla import SegmentOutput
    from repro.core.methods import METHODS
    from repro.core.protocol_engine import to_method_outputs
    from repro.core import protocols as P

    T = y.shape[1]
    ts = T0 + DT * np.arange(T, dtype=np.float64)
    sub = SegmentOutput(*(x[rows] for x in seg))
    ys = y[rows]
    decoders = {"implicit": P.decode_implicit,
                "singlestream": P.decode_singlestream,
                "singlestreamv": P.decode_singlestreamv}
    encoders = {"implicit": P.encode_implicit,
                "twostreams": lambda recs, mo: P.encode_twostreams(recs),
                "singlestream": lambda recs, mo: P.encode_singlestream(recs),
                "singlestreamv":
                    lambda recs, mo: P.encode_singlestreamv(recs)}
    for i, s in enumerate(rows):
        blob = got[s]
        dec = P.decode_twostreams(*blob, ts) if protocol == "twostreams" \
            else decoders[protocol](blob, ts)
        err = float(np.max(np.abs(np.asarray(dec) - ys[i])))
        check(err <= EPS * (1 + 1e-4) + 1e-4,
              f"{label}: stream {s} decodes {err} from its data (eps {EPS})")
    # The sequential codecs model joint and disjoint knots; continuous and
    # mixed knots are held to the decode bound above.
    if knot_kind in ("joint", "disjoint"):
        mos = to_method_outputs(sub, ts, ys, knot_kind=knot_kind)
        for i, s in enumerate(rows):
            recs = P.PROTOCOLS[protocol](mos[i], ts, ys[i])
            ref = encoders[protocol](recs, mos[i])
            check(ref == got[s],
                  f"{label}: stream {s} differs from the sequential codec")
    grid = np.arange(T, dtype=np.float64)
    agree = 0
    for i in range(len(rows)):
        out = METHODS[method](grid, ys[i].astype(np.float64), EPS,
                              max_run=cap)
        seq = np.zeros(T, bool)
        seq[[sg.i1 - 1 for sg in out.segments]] = True
        agree += bool(np.array_equal(seq, sub.breaks[i]))
    return agree


def fleet_phase(meter, devices, y, rng) -> None:
    from repro.core.evaluate import COMBINATIONS, METHOD_KNOT_KINDS
    from repro.core.protocol_engine import encode_batch
    from repro.core.protocols import PROTOCOL_CAPS
    from repro.sharding.fleet import FleetStream

    S, T = y.shape
    phase = meter.mark()
    total = 0
    for key, (method, protocol) in COMBINATIONS.items():
        kk = METHOD_KNOT_KINDS.get(method, "disjoint")
        cap = PROTOCOL_CAPS[protocol] or 256
        mark = meter.mark()
        fs = FleetStream(method, protocol, S, EPS, devices=devices, t0=T0,
                         dt=DT)
        got = run_fleet_stream(fs, y)
        nbytes = sum(map(wire_len, got))
        check(nbytes == fs.total_bytes, f"{key}: byte accounting")
        print(meter.line(f"fleet {key} {method}/{protocol} ingest", mark,
                         S * T, nbytes), flush=True)
        mark = meter.mark()
        seg = offline_segmentation(method, y, cap, devices[0])
        ref = encode_batch(seg, y, protocol, kk, t0=T0, dt=DT)
        bad = [s for s in range(S) if got[s] != ref[s]]
        check(not bad, f"{key}: {len(bad)} of {S} streams differ from "
                       f"encode_batch of the offline segmentation "
                       f"(first {bad[:5]})")
        rows = np.sort(rng.choice(S, N_SEQ, replace=False))
        agree = check_sequential(key, method, protocol, kk, cap, seg, y,
                                 rows, got)
        print(meter.line(f"fleet {key} checks", mark, S * T, nbytes,
                         f"streams_equal={S} sequential_ok={N_SEQ} "
                         f"methods_py_same_breaks={agree}/{N_SEQ}"),
              flush=True)
        total += nbytes
    print(meter.line("fleet", phase, S * T * len(COMBINATIONS), total),
          flush=True)


def serve_phase(meter, devices, rng) -> None:
    import jax.numpy as jnp
    from repro.core.evaluate import BATCHED_SEGMENTERS
    from repro.core.protocol_engine import encode_batch
    from repro.serving import ServeLoop, SlotManager

    method, protocol = "linear", "singlestream"
    n_churn = int(SERVE_SLOTS * CHURN)
    data = tsbs_cpu(rng, SERVE_SLOTS + N_TICKS * n_churn,
                    N_TICKS * TICK_WIDTH)
    mark = meter.mark()
    mgr = SlotManager(method, protocol, capacity=SERVE_SLOTS,
                      devices=devices, eps0=EPS)
    loop = ServeLoop(mgr, tick_width=TICK_WIDTH, queue_cap=TICK_WIDTH)
    delivered, fed = {}, {}

    def admit(row: int) -> str:
        sid = str(row)
        loop.admit(sid, eps=EPS)
        delivered[sid], fed[sid] = [], 0
        return sid

    def collect(wire) -> None:
        for sid, _, blob in wire:
            delivered[sid].append(blob)

    live = [admit(r) for r in range(SERVE_SLOTS)]
    next_row = SERVE_SLOTS
    evicted, points = [], 0
    for _ in range(N_TICKS):
        for sid in live:
            n = fed[sid]
            check(loop.offer(sid, data[int(sid), n:n + TICK_WIDTH])
                  == TICK_WIDTH, f"serve: stream {sid} shed samples")
            fed[sid] = n + TICK_WIDTH
        rep = loop.tick()
        points += rep.consumed
        collect(rep.wire)
        gone = set(rng.choice(len(live), n_churn, replace=False).tolist())
        for sid in [s for i, s in enumerate(live) if i in gone]:
            ev = loop.evict(sid)
            collect(ev.wire)
            delivered[sid].append(ev.tail)
            evicted.append(sid)
        live = [s for i, s in enumerate(live) if i not in gone]
        live += [admit(r) for r in range(next_row, next_row + n_churn)]
        next_row += n_churn
    wire_bytes = mgr.total_bytes
    print(meter.line(f"serve {method}/{protocol} ticks", mark, points,
                     wire_bytes, f"slots={SERVE_SLOTS} ticks={N_TICKS} "
                                 f"evicted={len(evicted)}"), flush=True)
    check_mark = meter.mark()
    by_len = {}
    for sid in evicted:
        by_len.setdefault(fed[sid], []).append(sid)
    for n, sids in sorted(by_len.items()):
        ys = np.stack([data[int(s), :n] for s in sids])
        seg = BATCHED_SEGMENTERS[method](jnp.asarray(ys), EPS, max_run=256)
        ref = encode_batch(seg, ys, protocol)
        bad = [s for s, r in zip(sids, ref) if b"".join(delivered[s]) != r]
        check(not bad, f"serve: {len(bad)} of {len(sids)} evicted streams "
                       f"of {n} points differ from their offline encode "
                       f"(first {bad[:5]})")
    print(meter.line("serve checks", check_mark, sum(fed[s] for s in evicted),
                     sum(len(b) for s in evicted for b in delivered[s]),
                     f"evicted_equal={len(evicted)}"), flush=True)


def store_phase(meter, devices, y, rng) -> None:
    from repro.sharding.fleet import FleetStream
    from repro.store import SegmentStore

    method, protocol = "linear", "singlestream"
    S, T = y.shape
    mark = meter.mark()
    store = SegmentStore(protocol, eps=EPS, t0=T0, dt=DT)
    fs = FleetStream(method, protocol, S, EPS, devices=devices, t0=T0,
                     dt=DT, store=store)
    run_fleet_stream(fs, y)
    print(meter.line(f"store {method}/{protocol} ingest", mark, S * T,
                     fs.total_bytes), flush=True)
    mark = meter.mark()
    kinds = ("avg", "min", "max", "corr")
    for q in range(N_QUERIES):
        kind = kinds[q % len(kinds)]
        lo = int(rng.integers(0, T - 2))
        hi = int(rng.integers(lo + 3, T + 1))
        keys = [int(k) for k in rng.choice(
            S, 2 if kind == "corr" else QUERY_STREAMS, replace=False)]
        t_lo, t_hi = T0 + DT * lo, T0 + DT * hi
        out = store.query(kind, keys, t_lo, t_hi)
        brute = store.scan(keys, t_lo, t_hi)
        label = f"store query {q} {kind} [{lo},{hi})"
        if kind == "corr":
            value, bound = out
            ref = np.corrcoef(brute[keys[0]], brute[keys[1]])[0, 1]
            check(abs(value - ref) <= bound + 1e-6,
                  f"{label}: {value} vs {ref}, bound {bound}")
            continue
        reduce = {"avg": np.mean, "min": np.min, "max": np.max}[kind]
        for key, (value, bound) in zip(keys, out):
            ref = reduce(brute[key])
            check(np.isfinite(value) and bound >= 0
                  and abs(value - ref) <= bound + 1e-6 * (1 + abs(value)),
                  f"{label} stream {key}: {value} vs {ref}, bound {bound}")
    print(meter.line("store queries", mark, 0, 0,
                     f"queries={N_QUERIES} answers_within_bound=all"),
          flush=True)


def mesh_phase(meter, devices, y) -> None:
    """The stream-sharded fleet path over ``devices`` against one device."""
    from repro.core.evaluate import COMBINATIONS, METHOD_KNOT_KINDS
    from repro.core.protocol_engine import encode_batch
    from repro.core.protocols import PROTOCOL_CAPS
    from repro.sharding.fleet import (FleetStream, fleet_encode, fleet_mesh,
                                      fleet_point_metrics)

    S, T = y.shape
    ym = y[:MESH_STREAMS]
    mesh_all = fleet_mesh(devices=devices)
    mesh_one = fleet_mesh(devices=devices[:1])
    for key in MESH_COMBOS:
        method, protocol = COMBINATIONS[key]
        kk = METHOD_KNOT_KINDS.get(method, "disjoint")
        cap = PROTOCOL_CAPS[protocol] or 256
        mark = meter.mark()
        fs = FleetStream(method, protocol, S, EPS, devices=devices, t0=T0,
                         dt=DT)
        got = run_fleet_stream(fs, y)
        print(meter.line(f"mesh {key} FleetStream x{len(devices)}", mark,
                         S * T, fs.total_bytes,
                         f"shard_bytes={fs.shard_bytes.tolist()}"),
              flush=True)
        seg = offline_segmentation(method, y, cap, devices[0])
        ref = encode_batch(seg, y, protocol, kk, t0=T0, dt=DT)
        bad = [s for s in range(S) if got[s] != ref[s]]
        check(not bad, f"mesh {key}: {len(bad)} of {S} streams differ from "
                       f"encode_batch of the one-device segmentation")

        mark = meter.mark()
        fm = fleet_point_metrics(ym, EPS, method, protocol, mesh=mesh_all)
        print(meter.line(f"mesh {key} fleet_point_metrics x{len(devices)}",
                         mark, ym.size, fm.fleet_nbytes,
                         f"fleet_means={fm.fleet_means}"), flush=True)
        one = fleet_point_metrics(ym, EPS, method, protocol, mesh=mesh_one)
        for name in ("ratio", "latency", "error"):
            check(np.array_equal(getattr(fm.metrics, name),
                                 getattr(one.metrics, name)),
                  f"mesh {key}: {name} differs from one device")
        check(np.array_equal(fm.nbytes, one.nbytes)
              and np.array_equal(fm.n_records, one.n_records),
              f"mesh {key}: per-stream bytes/records differ")
        check(fm.fleet_nbytes == one.fleet_nbytes
              == int(fm.shard_nbytes.sum()) == int(fm.nbytes.sum()),
              f"mesh {key}: psum of shard bytes")
        for name, v in fm.fleet_means.items():
            check(np.isclose(v, one.fleet_means[name], rtol=1e-5),
                  f"mesh {key}: pmean {name} {v} vs "
                  f"{one.fleet_means[name]}")
        check(fleet_encode(fm, ym, t0=T0, dt=DT)
              == encode_batch(one.seg, ym, protocol, kk, t0=T0, dt=DT),
              f"mesh {key}: sharded wire differs from one device")
        print(meter.line(f"mesh {key} checks", mark, ym.size,
                         one.fleet_nbytes,
                         f"streams_equal={S} metrics_equal={MESH_STREAMS}"),
              flush=True)


def check_compiled_kernels(jax) -> None:
    """A kernel must lower to a Mosaic custom call, not interpret mode."""
    import jax.numpy as jnp
    from repro.compat.pallas import interpret_mode
    from repro.kernels.ops import KERNEL_SEGMENTERS
    check(not interpret_mode(), "Pallas would run in interpret mode")
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    for method, fn in KERNEL_SEGMENTERS.items():
        check("tpu_custom_call" in fn.lower(x, EPS).as_text(),
              f"the {method} kernel does not lower to a TPU custom call")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the stream-sharded fleet path")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no src/repro beside {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.core.compile_meter import CompileMeter
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke.py: JAX found no devices: {e}", file=sys.stderr)
        return 2
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU; JAX found {dev0.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    used = devices[:args.chips]
    print(f"devices: using {len(used)} of {len(devices)} {dev0.platform} "
          f"({dev0.device_kind}); compile cache: {cache_dir}", flush=True)
    meter = PhaseClock(CompileMeter(jax))
    rng = np.random.default_rng(args.seed)
    try:
        check_compiled_kernels(jax)
        mark = meter.mark()
        y = tsbs_cpu(rng, HOSTS * METRICS, WINDOW * N_WINDOWS)
        print(meter.line("data tsbs cpu-only", mark, y.size, 0,
                         f"streams={y.shape[0]}"), flush=True)
        if args.chips == 4:
            mesh_phase(meter, used, y)
        else:
            fleet_phase(meter, used, y, rng)
            serve_phase(meter, used, rng)
            store_phase(meter, used, y[:STORE_STREAMS], rng)
    except CheckFailed as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
