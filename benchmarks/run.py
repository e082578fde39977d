# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows plus the full figure tables; JSON artifacts go to
# experiments/paper/.
from __future__ import annotations

import sys
import time


def main() -> None:
    t0 = time.time()
    from . import (figures, fleet_bench, framework_bench, protocol_bench,
                   serve_bench, store_bench, streaming_bench)

    csv_rows = []

    def fig(name, fn):
        t = time.time()
        res = fn()
        csv_rows.append((name, (time.time() - t) * 1e6, "figure"))
        return res

    all_results = {}
    all_results["gps"] = fig("fig12_gps", figures.fig12_gps)
    all_results["lidar"] = fig("fig13_lidar", figures.fig13_lidar)
    all_results["urban"] = fig("fig14_urban", figures.fig14_urban)
    all_results["ucr"] = fig("fig15_ucr", figures.fig15_ucr)
    fig("fig16_ranking", lambda: figures.fig16_ranking(all_results))
    fig("table1_features", figures.table1_features)
    claims = figures.table3_claims(all_results)

    csv_rows.extend(framework_bench.kernel_throughput())
    csv_rows.extend(streaming_bench.streaming_bench())  # -> BENCH_streaming.json
    csv_rows.extend(protocol_bench.protocol_bench())    # -> BENCH_protocols.json
    csv_rows.extend(framework_bench.adaptive_eps_bench())
    # Under this aggregator jax initialized long ago, so the fleet bench's
    # 8-fake-device XLA flag can't apply — the scaling sweep degrades to
    # the ambient device count; run it standalone for the full curve.
    csv_rows.extend(fleet_bench.fleet_bench())          # -> BENCH_fleet.json
    csv_rows.extend(serve_bench.serve_bench())          # -> BENCH_serve.json
    csv_rows.extend(store_bench.store_bench())          # -> BENCH_store.json

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    n_fail = sum(not v for v in claims.values())
    print(f"\n[benchmarks done in {time.time()-t0:.1f}s; "
          f"table3 claims: {len(claims)-n_fail}/{len(claims)} pass]")
    if n_fail:
        sys.exit(1)


if __name__ == '__main__':
    main()
