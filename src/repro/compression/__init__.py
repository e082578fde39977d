"""PLA stream compression of host-side metric streams.

- :mod:`telemetry` — host-side metric streams compressed with the paper's
  lowest-latency protocol (SingleStreamV).
"""

from .telemetry import TelemetryCompressor

__all__ = ["TelemetryCompressor"]
