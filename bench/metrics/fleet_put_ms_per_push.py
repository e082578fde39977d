"""Host time per fleet push in the program's ``repro.fleet.put`` spans: the
host-to-device put of each shard's columns (``jax.device_put``), summed
over the traced window, over the pushes made.

Reads ``run.trace.program_spans`` (``bench/core/program_spans.py``);
None where the trace holds no such span."""

from bench.core.program_spans import span_count, span_s

SPAN = "repro.fleet.put"


def read(run):
    spans = getattr(run.trace, "program_spans", None)
    pushes = run.records.get("pushes")
    if not spans or not pushes or not span_count(spans, SPAN):
        return None
    return 1e3 * span_s(spans, SPAN) / len(pushes)
