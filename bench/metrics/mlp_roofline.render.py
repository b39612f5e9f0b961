"""Roofline share of the Pallas MLP kernels, in %."""
from bench import readings


def read(run):
    return readings.mlp_roofline(run)
