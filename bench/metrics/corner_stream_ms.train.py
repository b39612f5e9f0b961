"""Device ms per step in ops over the hash grid's per-corner stream (points
x 8 corners x levels rows): the merged backward's sort, segment sums and
table scatter on the `ref` path.  The trace names ops by their HLO text,
in which a scatter shows only as a custom fusion, so the stream's length
is what picks them out."""
from bench import readings


def read(run):
    if run.trace is None or not run.window.get("units"):
        return None
    rows = readings.corner_stream_rows(run)
    s = sum(e.dur for e in run.trace.op_events() if readings.on_corner_stream(e, rows)) * 1e-9
    return 1e3 * s / run.window["units"] if s > 0 else None
