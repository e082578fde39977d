"""Host time of the serving slot plane's per-slot emitter loop per tick
that stepped: the program's ``repro.slots.emit`` spans, summed over the
traced window, over the ``repro.slots.step`` spans there that consumed
points (the drain ticks of evicts included).  A tick that consumed
nothing launches and emits nothing, and a window can hold many such
ticks while the client waits for samples to come due.

Reads ``run.trace.program_spans`` (``bench/core/program_spans.py``);
None where the trace holds no tick that stepped."""

from bench.core.program_spans import span_s


def read(run):
    spans = getattr(run.trace, "program_spans", None)
    if not spans or "ticks" not in run.records:
        return None
    stepped = sum(1 for name, _, _, args in spans
                  if name == "repro.slots.step" and args.get("points"))
    if not stepped:
        return None
    return 1e3 * span_s(spans, "repro.slots.emit") / stepped
