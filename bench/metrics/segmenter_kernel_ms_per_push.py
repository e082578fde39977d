"""Device time of the fleet's Pallas segmenter kernel per push: the union
of its operations' intervals in the traced window on each chip, as a
mean over the chips (each runs its own shard's launch), over the pushes
made."""

from bench.core.kernels import is_segmenter


def read(run):
    pushes = run.records.get("pushes")
    if run.trace is None or not pushes:
        return None
    match = is_segmenter(run.cell.traffic["method"])
    if not run.trace.op_count(match):
        return None
    return 1e3 * run.trace.op_s(match) / len(pushes)
