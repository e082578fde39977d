"""The benchmark's shared machinery: cell loading, the run harness, the
TSBS generator, the trace reduction, chip peaks, kernel byte counts and
the comparison that decides ``correct``."""
