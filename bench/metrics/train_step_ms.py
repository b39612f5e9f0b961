"""Host-clock time of the window over the units it completed, in ms."""


def read(run):
    w = run.window
    return 1e3 * w["seconds"] / w["units"] if w.get("units") else None
