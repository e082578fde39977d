"""Million-stream serving front-end (ROADMAP: churny admission).

Three layers over the fleet engine:

- :mod:`repro.serving.slots` — padded per-device slot plane with
  generation-tagged admission/eviction (masked per-row-clock segmenter
  rows + one wire emitter a shard);
- :mod:`repro.serving.ticks` — out-of-phase arrivals batched into
  fixed-shape per-tick pushes, bounded ingress queues, shed-or-block
  backpressure;
- :mod:`repro.serving.budget` — one egress budget in bytes/s,
  water-filled across live streams in log-ε space.
"""

from .budget import GlobalEpsBudget
from .slots import (EvictReport, FleetFull, INACTIVE_EPS, Slot,
                    SlotManager)
from .ticks import ServeLoop, TickReport

__all__ = [
    "GlobalEpsBudget", "EvictReport", "FleetFull", "INACTIVE_EPS", "Slot",
    "SlotManager", "ServeLoop", "TickReport",
]
