"""Share of the traced fleet window in which no operation ran on the
chip: 100 * (1 - busy / window), busy being the union of a chip's
device operations' intervals, as a mean over the chips used."""


def read(run):
    if run.trace is None or "pushes" not in run.records:
        return None
    return 100.0 * run.trace.idle_share()
