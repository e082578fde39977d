#!/usr/bin/env python3
"""Readings from which a cell's limits are set: the program's compared
numbers over many seeds, and the control's on the same samples.

    python3 bench/tools/readings.py --workload <name> --seeds 1,2,3 \
        --seconds 5

For each seed, in one process (so programs compile once), one run of the
cell's timed path as ``bench/run.py`` makes it, with a window of
``--seconds``, then the program's three numbers and the control's (the
plain reference with its samples held in bfloat16) on the same sampled
streams.  One JSON line per seed; the benchmark's own runs never run the
control.  Needs the cell's chips, like ``bench/run.py``.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default="",
                    help="directory for the samples and bytes of each "
                         "stream that passes a limit (.npz per seed)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench.core import check, harness
    from bench.core.cell import load_cell
    from bench.core.compile_meter import CompileMeter
    cell = load_cell(args.workload)
    harness._enable_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("readings: needs the cell's TPU chips", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    meter = CompileMeter(jax)
    tr, eps = cell.traffic, float(cell.config["eps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, used, meter, t)
        samples = out["samples"]
        ref = check.reference_answers(samples, tr["method"], tr["protocol"],
                                      eps, cell.system.MAX_RUN)
        got = check.decode_answers(samples, tr["protocol"])
        ctrl = check.reference_answers(samples, tr["method"],
                                       tr["protocol"], eps, cell.system.MAX_RUN,
                                       bf16=True)
        worst = _over_limits(cell, samples, got, ref, eps)
        if args.dump and worst:
            _dump(Path(args.dump), cell.name, seed,
                  [samples[i] for i, _ in worst])
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "pushes": len(out["records"].get("pushes", [])),
            "over_limits": [[int(samples[i].stream), n] for i, n in worst],
            "streams": len(samples),
            "samples": int(sum(s.ts.size for s in samples)),
            "program": check.numbers(samples, got, ref, eps),
            "control": check.numbers(samples, ctrl, ref, eps),
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


def _over_limits(cell, samples, got, ref, eps):
    """``(index, numbers)`` of each sampled stream that alone passes a
    limit of the cell."""
    from bench.core import check
    out = []
    for i in range(len(samples)):
        n = check.numbers(samples[i:i + 1], got[i:i + 1], ref[i:i + 1], eps)
        # One stream's share of mismatches is 0 or 100, and a stream of
        # another shape has no value gap: judge only what it reads.
        limits = {k: v for k, v in cell.limits.items()
                  if k != "shape_mismatch_pct" and n.get(k) is not None}
        if not all(r["ok"] for r in check.judge(n, limits)):
            out.append((i, n))
    return out


def _dump(where: Path, workload: str, seed: int, samples) -> None:
    import numpy as np
    where.mkdir(parents=True, exist_ok=True)
    arrays = {}
    for s in samples:
        arrays[f"ts_{s.stream}"] = s.ts
        arrays[f"ys_{s.stream}"] = s.ys
        blob = s.answer if isinstance(s.answer, bytes) else b"".join(s.answer)
        arrays[f"wire_{s.stream}"] = np.frombuffer(blob, np.uint8)
    np.savez_compressed(where / f"{workload}-{seed}.npz", **arrays)


if __name__ == "__main__":
    sys.exit(main())
