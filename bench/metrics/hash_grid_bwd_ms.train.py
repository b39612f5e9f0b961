"""Device ms per step in ops under the program's `hash_grid/bwd` scope: the
hash grid's backward (corner stream, sort, merge, table scatter) for every
grid the step trains."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "hash_grid/bwd")
