"""Serve a churny synthetic sensor fleet through the slot plane.

Paper scenario 1: streams come and go through the admission-controlled
front-end, :class:`repro.serving.ServeLoop`, with an optional fleet-wide
egress budget:

    PYTHONPATH=src python -m repro.launch.serve \
        --fleet-streams 32 --fleet-ticks 60 --churn 0.1 \
        --budget-bytes-per-s 2000
"""

import argparse
import time

import numpy as np

from repro.serving import GlobalEpsBudget, ServeLoop, SlotManager


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--fleet-streams", type=int, default=32,
                    help="live streams held in the slot plane")
    ap.add_argument("--fleet-capacity", type=int, default=0,
                    help="slot capacity (0: 2x the live streams)")
    ap.add_argument("--fleet-ticks", type=int, default=60)
    ap.add_argument("--tick-width", type=int, default=64)
    ap.add_argument("--churn", type=float, default=0.1,
                    help="fraction of live streams replaced per tick")
    ap.add_argument("--method", default="linear")
    ap.add_argument("--protocol", default="singlestream")
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--budget-bytes-per-s", type=float, default=0.0,
                    help="fleet egress budget (0: fixed eps, no "
                         "controller)")
    return ap


def serve_fleet(args) -> None:
    """Churny synthetic fleet through the admission-controlled loop."""
    rng = np.random.default_rng(0)
    cap = args.fleet_capacity or 2 * args.fleet_streams
    budget = None
    if args.budget_bytes_per_s > 0:
        budget = GlobalEpsBudget(args.budget_bytes_per_s,
                                 sample_hz=float(args.tick_width))
    mgr = SlotManager(args.method, args.protocol, capacity=cap,
                      eps0=args.eps)
    loop = ServeLoop(mgr, tick_width=args.tick_width,
                     queue_cap=8 * args.tick_width, budget=budget)

    def fresh(name):
        loop.admit(name, eps=args.eps)

    n_admitted = 0
    live = []
    for _ in range(args.fleet_streams):
        fresh(f"sensor-{n_admitted}")
        live.append(f"sensor-{n_admitted}")
        n_admitted += 1
    t0 = time.time()
    total_bytes = total_points = 0
    for k in range(args.fleet_ticks):
        # churn: replace a fraction of the fleet, out of phase
        for _ in range(int(len(live) * args.churn)):
            gone = live.pop(int(rng.integers(len(live))))
            rep = loop.evict(gone)
            total_bytes += len(rep.tail) \
                + sum(len(b) for _, _, b in rep.wire)
            fresh(f"sensor-{n_admitted}")
            live.append(f"sensor-{n_admitted}")
            n_admitted += 1
        for name in live:
            loop.offer(name, rng.normal(0, 1, args.tick_width)
                       .astype(np.float32).cumsum())
        rep = loop.tick()
        total_bytes += rep.nbytes
        total_points += rep.consumed
        if k % 10 == 0 or k == args.fleet_ticks - 1:
            pool = (f" pool={rep.budget_pool:.0f}B"
                    if rep.budget_pool is not None else "")
            print(f"tick {rep.tick:4d}: live={rep.live} "
                  f"consumed={rep.consumed} bytes={rep.nbytes} "
                  f"eps=[{rep.eps_lo:.3g}, {rep.eps_hi:.3g}]"
                  f"{pool} shed={rep.shed_total} "
                  f"wait={1e3 * rep.wait_mean_s:.2f}ms "
                  f"(max {1e3 * rep.wait_max_s:.2f}ms)")
    dt_s = time.time() - t0
    print(f"served {total_points} points / {total_bytes} wire bytes "
          f"across {n_admitted} stream admissions in {dt_s:.2f}s "
          f"({total_points / max(dt_s, 1e-9):,.0f} pts/s)")


def main(argv=None):
    serve_fleet(build_parser().parse_args(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
