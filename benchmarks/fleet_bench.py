"""Fleet-ingest benchmarks: sharded (S, T) pipeline scaling + wire packer.

Times the stream-sharded fleet pipeline of :mod:`repro.sharding.fleet`
at growing device counts (1 / 2 / 4 / 8 host-platform devices — the CI
CPU runner fakes them with ``--xla_force_host_platform_device_count``,
set below *before* jax imports), the end-to-end metrics path
(:func:`repro.sharding.fleet.fleet_point_metrics`), and the wire packer
:class:`repro.core.protocol_engine.ProtocolEmitter` on its dense-event
worst case (every point a singleton).  Results land in the
top-level ``BENCH_fleet.json`` so the scaling curve is tracked across
PRs like the other three benches.

``BENCH_SMOKE=1`` shrinks the batch for CI smoke runs.
"""

from __future__ import annotations

import json
import os

# Must precede any jax import: fake a multi-device host platform so the
# scaling sweep is meaningful on single-CPU CI runners.
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from .framework_bench import _time as _time_us          # noqa: E402
from repro.core import jax_pla                          # noqa: E402
from repro.core.protocol_engine import ProtocolEmitter  # noqa: E402
from repro.sharding import fleet                        # noqa: E402

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_fleet.json")

SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))
S, T = (64, 2048) if SMOKE else (256, 16384)
EPS = 1.0
ITERS = 3
METHOD, PROTOCOL = "angle", "singlestream"


def _stream_batch(seed=0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0, 0.5, (S, T)), axis=1).astype(np.float32)


def _time(fn) -> float:
    return _time_us(fn, iters=ITERS) / 1e6


def fleet_bench():
    """CSV rows for benchmarks.run + the BENCH_fleet.json artifact."""
    y = _stream_batch()
    points = S * T
    n_dev = jax.device_count()
    counts = [d for d in (1, 2, 4, 8) if d <= n_dev and S % d == 0]
    report = {
        "config": {"streams": S, "t_len": T, "eps": EPS, "method": METHOD,
                   "protocol": PROTOCOL, "iters": ITERS, "smoke": SMOKE,
                   "backend": jax.default_backend(), "devices": n_dev},
        "scaling": {}, "packer": {},
    }
    rows = []

    import jax.numpy as jnp
    eps_arr = jnp.full((S,), EPS, jnp.float32)
    base = None
    for d in counts:
        mesh = fleet.fleet_mesh(d)
        # Device part only (segment + descriptors + metrics + psum): the
        # float64 host finish is timed separately via fleet_point_metrics.
        fn = fleet._fleet_pipeline(mesh, METHOD, PROTOCOL, "disjoint",
                                   256, 127)
        ys = fleet.fleet_shard(y, mesh)
        # Block on the psum'd fleet total: one output of the single XLA
        # executable, ready only when the whole pipeline ran.
        sec = _time(lambda: fn(ys, eps_arr)[5])
        base = base or sec
        report["scaling"][str(d)] = {
            "seconds": sec, "points_per_s": points / sec,
            "speedup_vs_1dev": base / sec,
        }
        rows.append((f"fleet/devices={d}", sec * 1e6,
                     f"{points / sec / 1e6:.1f}Mpts/s "
                     f"x{base / sec:.2f}"))
    # End-to-end metrics pipeline (fleet_point_metrics, float64 host
    # finish included) on the widest mesh.
    wide_mesh = fleet.fleet_mesh(counts[-1])
    e2e_m = _time(lambda: fleet.fleet_point_metrics(
        y, EPS, METHOD, PROTOCOL, mesh=wide_mesh))
    report["scaling"]["end_to_end_metrics"] = {
        "seconds": e2e_m, "points_per_s": points / e2e_m}
    rows.append((f"fleet/e2e-metrics@{counts[-1]}dev", e2e_m * 1e6,
                 f"{points / e2e_m / 1e6:.1f}Mpts/s"))

    # Fused packer, dense-event worst case: every point breaks, so every
    # event packs a record (ROADMAP: the per-event Python byte assembly
    # this packer replaced was the bottleneck exactly here).
    dense = np.random.default_rng(1).normal(0, 50, (S, T)) \
        .astype(np.float32)
    seg = jax_pla.disjoint_segment(dense, 1e-6, max_run=127)
    ev = jax_pla.SegmentOutput(np.asarray(seg.breaks), np.asarray(seg.a),
                               np.asarray(seg.v))
    dense64 = np.asarray(dense, np.float64)
    for proto in ("singlestream", "singlestreamv", "implicit"):
        def pack(proto=proto):
            em = ProtocolEmitter(proto, S)
            n = 0
            for lo in range(0, T, 1024):
                evc = jax_pla.SegmentOutput(ev.breaks[:, lo:lo + 1024],
                                            ev.a[:, lo:lo + 1024],
                                            ev.v[:, lo:lo + 1024])
                for b in em.step_chunk(evc, dense64[:, lo:lo + 1024]):
                    n += len(b)
            for b in em.flush():
                n += len(b)
            return n
        wire = pack()
        sec = _time(pack)
        report["packer"][proto] = {
            "seconds": sec, "points_per_s": points / sec,
            "bytes_per_s": wire / sec, "wire_bytes": wire,
        }
        rows.append((f"fleet/packer/{proto}", sec * 1e6,
                     f"{points / sec / 1e6:.1f}Mpts/s "
                     f"{wire / sec / 1e6:.0f}MB/s"))

    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
    return rows


if __name__ == "__main__":
    # Run as a module: PYTHONPATH=src python -m benchmarks.fleet_bench
    # (BENCH_SMOKE=1 shrinks the sweep).
    for name, us, derived in fleet_bench():
        print(f"{name},{us:.1f},{derived}")
    print(f"[wrote {os.path.abspath(OUT_PATH)}]")
