"""Device idle share of the traced window, in %: 1 - busy / window."""
from bench import readings


def read(run):
    return readings.idle_share(run)
