"""Seconds from the start of the process to the start of the window:
imports, data generation, building the system, compiling or loading
every program, and the warm-up."""


def read(run):
    return run.setup_s
