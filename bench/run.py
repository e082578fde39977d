#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints one JSON result as its last line (see ``bench/core/harness.py``).
Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the system under test (``src/repro``)
is not beside it.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no system under test at {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        sys.exit(2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.core.harness import main
    sys.exit(main(t_start=T_START))
