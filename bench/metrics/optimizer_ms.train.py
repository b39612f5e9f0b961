"""Device ms per step in ops under the program's `optimizer/adam` scope."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "optimizer/adam")
