"""Device ms per view in ops under the program's `hash_grid/fwd` scope: the
hash-grid encode of the view's points, every chunk."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "hash_grid/fwd")
