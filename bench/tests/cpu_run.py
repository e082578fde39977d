"""A whole run of one cell on the CPU at a tiny size, optionally with the
timed path broken underneath (for the tests; never a measurement).

    python -m bench.tests.cpu_run <workload> <seed> <seconds> [fault]

Faults: ``state_unchanged`` (the segmenter's step hands back the state
it was given), ``half_dropped`` (half of the streams' answers are left
out), ``answer_altered`` (each emitted blob has a byte changed where the
emitter produces it).  There is one chip, so no exchange between chips
to leave out.
"""

import sys

TINY = {           # config, traffic, the system module's constants
    "fleet": ({"hosts": 32, "metrics_per_host": 4}, {"push_width": 256},
              {"DATA_BLOCKS": 2, "SAMPLE_STREAMS": 8}),
    "serve": ({"slots": 64},
              {"rate_per_stream": 200.0, "churn_per_s": 20.0,
               "tick_width": 16},
              {"WARM_MAX_S": 2.0, "SAMPLE_STREAMS": 8}),
}


def shrink(cell) -> None:
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
    elif fault == "answer_altered":
        emit = ProtocolEmitter.step_chunk
        ProtocolEmitter.step_chunk = \
            lambda self, *a, **k: [_alter(b) for b in emit(self, *a, **k)]
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv) -> int:
    from bench.core.harness import main as run
    workload, seed, seconds = argv[:3]
    if len(argv) > 3:
        plant(argv[3])
    return run(["--workload", workload, "--seed", seed, "--seconds", seconds,
                "--trace", "0"], require_chip=False, adjust=shrink)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
