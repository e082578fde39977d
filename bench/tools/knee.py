#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell to find its knee: the highest
rate the system sustains without a growing backlog.

    python3 bench/tools/knee.py --workload serve-devops-L2-churn \
        --rates 50,100,150 --seconds 10 [--seed 1]

For each rate (samples per second per stream; the churn rate scales with
it), one window of the cell's own path in one process, and one JSON line:
ticks, samples consumed and still due at the end, latency quantiles.
The cell's traffic file then fixes its rate at about four fifths of the
knee; the benchmark's own runs never search for one.
"""

import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench.core import harness
    from bench.core.cell import load_cell
    from bench.core.compile_meter import CompileMeter
    harness._enable_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    meter = CompileMeter(jax)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = load_cell(args.workload)
        base = cell.traffic["rate_per_stream"]
        cell.traffic["rate_per_stream"] = rate
        cell.traffic["churn_per_s"] *= rate / base
        t = time.perf_counter()
        out = harness.run_cell(cell, args.seed, args.seconds,
                               devices[:cell.chips], meter, t)
        rec = out["records"]
        lat = rec.get("latencies_s", np.zeros(0))
        q = (np.percentile(lat, [50, 95, 99]) * 1e3).tolist() \
            if lat.size else None
        print(json.dumps({
            "rate_per_stream": rate,
            "offered_per_s": rate * cell.config["slots"],
            "consumed_per_s": (lat.size - rec.get("backlog_end", 0))
            / args.seconds,
            "warm_s": rec.get("warm_s"),
            "backlog_start": rec.get("backlog_start"),
            "ticks": rec.get("ticks"), "backlog_end": rec.get("backlog_end"),
            "latency_ms_p50_p95_p99": q, "error": out["error"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
