"""What the per-layer metrics need to know of the segmenter kernels: how
to find one in a trace, and the bytes a launch has to move.

A segmenter reads each sample once (float32, 4 B) and writes, for each
sample position, whether a segment ends there and the line it ends on:
a break flag (1 B), a slope and a value (float32, 4 B each).  That is
13 B per (stream, step) whatever implements it; the carried state between
launches and any widening of the flag are the implementation's choice
and are not counted, so a share of the bandwidth roofline computed from
these bytes cannot exceed what the device could reach.
"""

from __future__ import annotations

LANES = 128          # streams are padded to whole 128-lane blocks
SAMPLE_BYTES = 4
EVENT_BYTES = 1 + 4 + 4


def segmenter_bytes(n_streams: int, n_steps: int, chips: int = 1) -> int:
    """The bytes one chip's launch moves over ``n_steps`` steps, where
    ``n_streams`` streams are split row-wise over ``chips`` chips: its
    shard of ``n_streams // chips`` rows, padded to whole 128-lane
    blocks."""
    s_pad = -(-(n_streams // chips) // LANES) * LANES
    return s_pad * n_steps * (SAMPLE_BYTES + EVENT_BYTES)


def is_segmenter(method: str):
    """Selects a device operation of ``method``'s Pallas segmenter: by the
    jitted launcher that holds it, ``<method>_pallas``
    (``repro/kernels/<method>.py``), in the operation's or its module's
    name, or by the kernel body's own name, ``_<method>_kernel``."""
    names = (f"{method}_pallas", f"_{method}_kernel")
    return lambda op: any(n in op.name or n in op.module for n in names)
