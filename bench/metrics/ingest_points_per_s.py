"""Points pushed per second over the window: every point of every push
that ran in it, over the wall time from the first push's start to the
last push's return."""


def read(run):
    pushes = run.records.get("pushes")
    if not pushes:
        return None
    points = sum(p for _, _, p in pushes)
    return points / (pushes[-1][1] - pushes[0][0])
