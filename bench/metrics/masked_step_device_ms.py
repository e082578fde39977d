"""Device time of the serving engine's masked segmenter step per tick: the
summed durations of the operations of the ``_masked_scan`` jit
(``repro/core/jax_pla.py``, driven by ``masked_step_chunk``) in the
traced window, over the ticks made."""


def read(run):
    ticks = run.records.get("ticks")
    if run.trace is None or not ticks:
        return None

    def match(op):
        return "_masked_scan" in op.module

    if not run.trace.op_count(match):
        return None
    return 1e3 * run.trace.op_s(match) / ticks
