"""``fleet_pack_overlap`` on synthetic ``repro.fleet.emit`` spans: how
many shards pack at once while any packs."""

import pytest

from bench.core.cell import BENCH_DIR, load_module
from bench.tests.test_trace import _Run, _trace

READ = load_module(BENCH_DIR / "metrics" / "fleet_pack_overlap.py").read


def _emits(*shard_spans):
    """``repro.fleet.emit`` spans of shards 0, 1, .. from their
    ``(start, end)`` intervals."""
    return [("repro.fleet.emit", a, b, {"shard": d, "blocks": 4})
            for d, ivs in enumerate(shard_spans) for a, b in ivs]


def _read(spans):
    trace = _trace()
    trace.program_spans = spans
    return READ(_Run(trace, {"pushes": [(0.0, 1.0, 1)]}, {"method": "angle"}))


@pytest.mark.parametrize("spans,overlap", [
    # Four shards, two pushes, each shard after the last.
    (_emits([(0.0, 0.1), (1.0, 1.1)], [(0.1, 0.2), (1.1, 1.2)],
            [(0.2, 0.3), (1.2, 1.3)], [(0.3, 0.4), (1.3, 1.4)]), 1.0),
    # Two shards that pack at the same time.
    (_emits([(0.0, 0.2), (1.0, 1.2)], [(0.0, 0.2), (1.0, 1.2)]), 2.0),
    # Two at once, then one alone as long: 3 shard-seconds in 2.
    (_emits([(0.0, 0.1)], [(0.0, 0.1)], [(0.1, 0.2)]), 1.5),
    # One shard.
    (_emits([(0.0, 0.1), (0.5, 0.7)]), 1.0),
], ids=["in-turn", "two-at-once", "mixed", "one-shard"])
def test_fleet_pack_overlap(spans, overlap):
    assert _read(spans) == pytest.approx(overlap)


def test_fleet_pack_overlap_finds_nothing():
    """No reading without the spans, without their ``shard`` argument (the
    program before it named its shards), or without a trace."""
    no_shard = [("repro.fleet.emit", 0.0, 0.1, {"blocks": 4}),
                ("repro.fleet.fetch", 0.1, 0.2, {"shard": 0})]
    assert _read(no_shard) is None
    assert _read([]) is None
    assert READ(_Run(None, {"pushes": [(0.0, 1.0, 1)]},
                     {"method": "angle"})) is None
