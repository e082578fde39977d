"""A whole run of one cell on the CPU at a tiny size, optionally with the
timed path broken underneath (for the tests; never a measurement).

    python -m bench.tests.cpu_run <workload> <seed> <seconds> [fault] \
        [--chips N]

The run gets as many CPU devices as the cell has chips: the count is
added to ``XLA_FLAGS`` (``--xla_force_host_platform_device_count``)
before JAX starts.  ``--chips N`` runs an existing cell at another chip
count, the fleet split over N devices; it is for these tests alone, and
nothing on the chip path reads it.

Faults: ``state_unchanged`` (the segmenter's step hands back the state
it was given), ``half_dropped`` (half of the streams' answers are left
out), ``answer_altered`` (each emitted blob has a byte changed where the
emitter produces it), and, where a cell runs on more than one chip,
``shard_misplaced`` (``FleetStream.push`` hands back its shards' outputs
rotated by one shard, so shard d's rows carry shard d+1's bytes) and
``shard_altered`` (as ``answer_altered``, but in the last shard's rows
alone, which the check sees only where it samples every shard).  The
shards of a fleet exchange nothing, so no fault leaves out an exchange
between chips.
"""

import argparse
import os
import sys

TINY = {           # config, traffic, the system module's constants
    "fleet": ({"hosts": 32, "metrics_per_host": 4}, {"push_width": 256},
              {"DATA_BLOCKS": 2, "SAMPLE_STREAMS": 8}),
    "serve": ({"slots": 64},
              {"rate_per_stream": 200.0, "churn_per_s": 20.0,
               "tick_width": 16},
              {"WARM_MAX_S": 2.0, "SAMPLE_STREAMS": 8}),
}


def shrink(cell, chips=None) -> None:
    """Cut the cell to the tiny size; ``chips`` overrides its chip
    count."""
    if chips:
        cell.chips = chips
    conf, traffic, consts = TINY[cell.config["system"]]
    cell.config.update(conf)
    cell.traffic.update(traffic)
    for name, value in consts.items():
        setattr(cell.system, name, value)


def _alter(blob):
    if isinstance(blob, tuple):
        return tuple(_alter(b) for b in blob)
    if not blob:
        return blob
    return blob[:-1] + bytes([blob[-1] ^ 0x40])


def plant(fault: str) -> None:
    from repro.core import jax_pla
    from repro.core.protocol_engine import ProtocolEmitter
    from repro.kernels.ops import StreamingSegmenter
    from repro.serving.slots import SlotManager
    from repro.sharding.fleet import FleetStream

    if fault == "state_unchanged":
        push = StreamingSegmenter.push

        def push_keeping_state(self, y):
            carry = self._carry
            out = push(self, y)
            self._carry = carry
            return out

        StreamingSegmenter.push = push_keeping_state
        step = jax_pla.masked_step_chunk
        jax_pla.masked_step_chunk = \
            lambda state, y, lengths: (state, step(state, y, lengths)[1])
    elif fault == "half_dropped":
        fpush = FleetStream.push

        def push_half(self, y):
            out = fpush(self, y)
            half = len(out) // 2
            return out[:half] + [type(b)() for b in out[half:]]

        FleetStream.push = push_half
        sstep = SlotManager.step
        SlotManager.step = lambda self, plane, lengths: [
            w for w in sstep(self, plane, lengths) if int(w[0]) % 2 == 0]
    elif fault == "shard_misplaced":
        fpush = FleetStream.push

        def push_rotated(self, y):
            out = fpush(self, y)
            rows = len(out) // self.n_devices
            return out[rows:] + out[:rows]

        FleetStream.push = push_rotated
    elif fault == "shard_altered":
        fpush = FleetStream.push

        def push_altering_last(self, y):
            out = fpush(self, y)
            first = len(out) - len(out) // self.n_devices
            return out[:first] + [_alter(b) for b in out[first:]]

        FleetStream.push = push_altering_last
    elif fault == "answer_altered":
        emit = ProtocolEmitter.step_chunk
        ProtocolEmitter.step_chunk = \
            lambda self, *a, **k: [_alter(b) for b in emit(self, *a, **k)]
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="python -m bench.tests.cpu_run")
    ap.add_argument("workload")
    ap.add_argument("seed")
    ap.add_argument("seconds")
    ap.add_argument("fault", nargs="?")
    ap.add_argument("--chips", type=int)
    args = ap.parse_args(argv)
    from bench.core.cell import load_cell
    chips = args.chips or load_cell(args.workload).chips
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={chips}".strip()
    if args.fault:
        plant(args.fault)
    from bench.core.harness import main as run
    return run(["--workload", args.workload, "--seed", args.seed,
                "--seconds", args.seconds, "--trace", "0"],
               require_chip=False, adjust=lambda c: shrink(c, args.chips))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
