"""Ticks an evict runs to drain the leaving stream's queue: the
``repro.serve.tick`` spans nested in a ``repro.serve.evict`` span of the
traced window, over the evicts there.

Reads ``run.trace.program_spans`` (``bench/core/program_spans.py``);
None where the trace holds no evict span."""

from bench.core.program_spans import nested_count, span_count


def read(run):
    spans = getattr(run.trace, "program_spans", None)
    if not spans or "ticks" not in run.records:
        return None
    evicts = span_count(spans, "repro.serve.evict")
    if not evicts:
        return None
    return nested_count(spans, "repro.serve.tick",
                        "repro.serve.evict") / evicts
