"""Device time of the fleet's Pallas segmenter kernel per push: the summed
durations of its operations in the traced window over the pushes made."""

from bench.core.kernels import is_segmenter


def read(run):
    pushes = run.records.get("pushes")
    if run.trace is None or not pushes:
        return None
    match = is_segmenter(run.cell.traffic["method"])
    if not run.trace.op_count(match):
        return None
    return 1e3 * run.trace.op_s(match) / len(pushes)
