"""The JAX API surface that moves between releases, in one place.

The repo supports the one installed JAX (0.9).  Pallas-TPU launch
settings live in :mod:`repro.compat.pallas`, mesh and shard_map
spellings in :mod:`repro.compat.sharding`.

Policy: **no module outside this package may reference those attributes
directly** (``tests/test_compat.py``), so a JAX upgrade that renames one
is a change to this package only.
"""

from . import pallas, sharding  # noqa: F401

__all__ = ["pallas", "sharding"]
