"""How long a sample waits in ``ServeLoop``'s queue, from the ``offer``
that queued it to the tick that drains it: the mean of the
``wait_mean_ms`` argument of the traced window's ``repro.slots.step``
spans, weighted by their ``points``.

Reads ``run.trace.program_spans`` (``bench/core/program_spans.py``);
None where the trace holds no such span."""

from bench.core.program_spans import weighted_arg


def read(run):
    spans = getattr(run.trace, "program_spans", None)
    if not spans or "ticks" not in run.records:
        return None
    return weighted_arg(spans, "repro.slots.step", "wait_mean_ms", "points")
