"""One run of one cell: set up, measure a window, check the answers.

The last line on standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in
a traced run), and last ``checks``: each compared number with its limit.
The same numbers are the last lines on standard error.  With ``--trace
0`` the metrics are the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and the metrics are its per-layer
ones.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Optional

from bench.core import check
from bench.core.cell import ROOT, load_cell


class Run:
    """What a metric reader gets: the cell, the set-up time, the window's
    records, the reduced trace (traced runs only) and the device."""

    def __init__(self, cell, setup_s, records, trace, device_kind):
        self.cell = cell
        self.setup_s = setup_s
        self.records = records
        self.trace = trace
        self.device_kind = device_kind


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell, seed: int, seconds: float, used, meter, t_start: float,
             trace: bool = False) -> dict:
    """Set up, run the window (under the profiler when ``trace``), and
    collect the sampled answers.  ``setup_s`` counts from ``t_start``."""
    import jax
    system = cell.system.System(cell, seed, used)
    records = {"attempted": 0, "failed": 0}
    error = trace_dir = None
    setup_s = peak = None
    try:
        system.setup(seconds)
        setup_s = time.perf_counter() - t_start
        before = meter.snapshot()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # Host spans and device operations; no Python function trace,
            # which would slow the host-bound window it measures.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                records = system.window(seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        after = meter.snapshot()
        peak = _memory_peak(used)
        in_window = (after["compiles"] - before["compiles"]
                     + after["cache_hits"] - before["cache_hits"])
        _say(f"set-up {setup_s:.3f} s ({before['compiles']} compiles, "
             f"{before['compile_s']:.3f} s compiling, "
             f"{before['cache_hits']} cache hits); compiles or cache "
             f"loads inside the window: {in_window}")
        for key in ("warm_ticks", "warm_s", "backlog_start", "ticks",
                    "backlog_end", "offer_lag_max_s"):
            if key in records:
                _say(f"window {key}: {records[key]}")
    except Exception:      # the system under test failed: not correct
        error = traceback.format_exc()
    reduced = None
    if trace_dir and error is None:
        from bench.core.trace import load_trace
        reduced = load_trace(trace_dir, n_devices=len(used))
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    samples = []
    if error is None:
        try:
            samples = system.answers()
        except Exception:
            error = traceback.format_exc()
    system.close()
    return {"setup_s": setup_s, "records": records, "trace": reduced,
            "peak": peak, "samples": samples, "error": error}


def program_numbers(cell, samples) -> dict:
    """The compared numbers of the program's answers."""
    tr, eps = cell.traffic, float(cell.config["eps"])
    got = check.decode_answers(samples, tr["protocol"])
    ref = check.reference_answers(samples, tr["method"], tr["protocol"],
                                  eps, cell.system.MAX_RUN)
    return check.numbers(samples, got, ref, eps)


def main(argv=None, *, t_start: Optional[float] = None,
         require_chip: bool = True, adjust=None) -> int:
    """Run one cell.  ``require_chip=False`` and ``adjust`` (a function
    that edits the loaded cell) are for the CPU rehearsal tests only; a
    run without a chip reports no metrics."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        _say(f"cannot load cell: {e}")
        return 2
    if adjust is not None:
        adjust(cell)
    import jax
    cache_dir = _enable_compile_cache(jax)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _say(f"JAX found no devices: {e}")
        return 2
    on_chip = devices[0].platform == "tpu"
    if require_chip and not on_chip:
        _say(f"needs a TPU; JAX found {devices[0].platform}")
        return 2
    if len(devices) < cell.chips:
        _say(f"the cell needs {cell.chips} chips; JAX found {len(devices)}")
        return 2
    used = devices[:cell.chips]
    _say(f"{cell.name}: {len(used)} x {used[0].device_kind} "
         f"({used[0].platform}); compile cache {cache_dir}")

    from bench.core.compile_meter import CompileMeter
    meter = CompileMeter(jax)
    out = run_cell(cell, args.seed, args.seconds, used, meter, t_start,
                   trace=bool(args.trace))
    records, trace, samples = out["records"], out["trace"], out["samples"]
    setup_s = out["setup_s"]
    if out["error"] is not None:
        _say(f"the system under test failed:\n{out['error']}")
        rows = [{"name": "completed", "value": 0, "limit": 1, "ok": False}]
    else:
        t_check = time.perf_counter()
        nums = program_numbers(cell, samples)
        rows = check.judge(nums, cell.limits)
        _say(f"checked {len(samples)} sampled streams, "
             f"{sum(s.ts.size for s in samples)} samples, against the "
             f"plain reference in {time.perf_counter() - t_check:.3f} s")
    correct = all(r["ok"] for r in rows)

    metrics = {}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": out["peak"]}
    breakdown = None
    if on_chip and out["error"] is None:
        run = Run(cell, setup_s, records, trace, used[0].device_kind)
        wanted = cell.per_layer if args.trace else cell.end_to_end
        for m in wanted:
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace.busy_s()
            device["window_s"] = trace.window_s()
            breakdown = trace.breakdown()
    result = {"correct": correct, "attempted": records["attempted"],
              "failed": records["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {r["name"]: {"value": r["value"],
                                    "limit": r["limit"]} for r in rows}
    for r in rows:
        print(f"check {r['name']}: {r['value']} (limit {r['limit']}) "
              f"{'ok' if r['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
