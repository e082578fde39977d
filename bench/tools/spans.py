#!/usr/bin/env python3
"""Where a traced window's host time goes, by the system's own spans.

    python3 bench/tools/spans.py --workload fleet-devops-L2 \
        --seeds 1,2,3 --seconds 30

For each seed, in one process, one traced window of the cell's timed
path as ``bench/run.py --trace 1`` makes it, and the trace reduced as
the benchmark reduces it (``bench/core/trace.py``, which keeps the
system's ``repro.*`` spans too).  One JSON line per seed: the cell's
per-layer metrics and the program-span metrics
(``bench/metrics/fleet_*_ms_per_push.py``, ``serve_*.py``), the seconds,
count and longest instance of each span, the share of each outer span
its phases cover, and the device's idle time by the innermost span of
either family.
Needs the cell's chips, like ``bench/run.py``; the benchmark's own runs
never run it.
"""

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

PROGRAM_METRICS = ("fleet_put_ms_per_push", "fleet_fetch_ms_per_push",
                   "fleet_emit_ms_per_push", "serve_emit_ms_per_tick",
                   "serve_queue_wait_ms", "serve_drain_ticks_per_evict")
# Each outer span with the phases that should cover it.
COVER = {
    "bench.fleet.push": ("repro.fleet.put", "repro.fleet.segment",
                         "repro.fleet.fetch", "repro.fleet.emit"),
    "repro.serve.tick": ("repro.serve.drain", "repro.slots.step",
                         "repro.serve.budget", "repro.serve.report"),
    "repro.slots.step": ("repro.slots.dispatch", "repro.slots.fetch",
                         "repro.slots.emit"),
    "bench.serve.tick": ("repro.serve.tick",),
    "bench.serve.evict": ("repro.serve.evict",),
}


def traced_window(cell, seed: int, seconds: float, used):
    """Set up the cell's system, run one window under the profiler, and
    reduce the trace; the window's records and the reduced trace, which
    carries the program spans as ``program_spans``."""
    import jax
    from bench.core.trace import WINDOW_SPAN, load_trace
    system = cell.system.System(cell, seed, used)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        system.setup(seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                records = system.window(seconds)
        finally:
            jax.profiler.stop_trace()
        trace = load_trace(trace_dir, n_devices=len(used))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        system.close()
    return records, trace


def report(cell, records, trace, device_kind: str, on_chip: bool) -> dict:
    """The JSON line of one traced window.  Off the chip it holds no
    metric and no device time."""
    from bench.core import program_spans as ps
    from bench.core.harness import Run
    spans = trace.program_spans
    every = spans + [(n, a, b, {}) for n, a, b in trace.spans]
    names = sorted({s[0] for s in every})
    out = {"workload": cell.name,
           "window": {k: records[k] for k in ("attempted", "ticks")
                      if k in records},
           "spans": {n: {"s": ps.span_s(every, n),
                         "n": ps.span_count(every, n),
                         "max_s": max(b - a for m, a, b, _ in every
                                      if m == n)} for n in names},
           "cover": {p: ps.covered_share(every, p, kids)
                     for p, kids in COVER.items() if p in names}}
    if on_chip:
        run = Run(cell, None, records, trace, device_kind)
        wanted = [m["name"] for m in cell.per_layer] + list(PROGRAM_METRICS)
        out["metrics"] = {m: cell.reader(m)(run) for m in wanted}
        out["device"] = {"kind": device_kind, "busy_s": trace.busy_s(),
                         "window_s": trace.window_s()}
        out["idle_gaps"] = trace.idle_gaps()
    return out


def main(argv=None, *, require_chip: bool = True, adjust=None) -> int:
    """``require_chip=False`` and ``adjust`` (a function that edits the
    loaded cell) are for a rehearsal on the CPU only."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench.core import harness
    from bench.core.cell import load_cell
    cell = load_cell(args.workload)
    if adjust is not None:
        adjust(cell)
    harness._enable_compile_cache(jax)
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    if require_chip and not on_chip:
        print("spans: needs a TPU", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        records, trace = traced_window(cell, seed, args.seconds, used)
        line = report(cell, records, trace, used[0].device_kind, on_chip)
        line.update(seed=seed, wall_s=time.perf_counter() - t)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
