"""Device-idle ms per view inside the serving layer's host phases: the
program's `serve3d/render_prepare`, `serve3d/render_dispatch` and
`serve3d/render_readback` spans, as they stand on the profiler clock."""
from bench import scopes

SPANS = ("serve3d/render_prepare", "serve3d/render_dispatch", "serve3d/render_readback")


def read(run):
    return scopes.idle_inside_ms(run, SPANS)
