"""How many shards of a fleet pack at once, on average while any packs:
the seconds of each shard's ``repro.fleet.emit`` spans (grouped by their
``shard`` argument), summed over the shards, over the seconds of the
union of all of them.  1.0 where the shards pack one after another, up to
the shard count where they all pack at the same time.

Reads ``run.trace.program_spans`` (``bench/core/program_spans.py``);
None where the trace holds no such span, or none that names its shard."""

from bench.core.trace import _union

SPAN = "repro.fleet.emit"


def _seconds(intervals) -> float:
    return sum(b - a for a, b in _union(intervals))


def read(run):
    spans = getattr(run.trace, "program_spans", None) or ()
    by_shard = {}
    for name, a, b, args in spans:
        if name == SPAN and "shard" in args:
            by_shard.setdefault(args["shard"], []).append((a, b))
    if not by_shard:
        return None
    every = _seconds([iv for ivs in by_shard.values() for iv in ivs])
    if every <= 0:
        return None
    return sum(_seconds(ivs) for ivs in by_shard.values()) / every
