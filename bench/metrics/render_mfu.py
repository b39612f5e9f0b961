"""Model FLOPs of the window over the chip bf16 peak, in %."""
from bench import readings


def read(run):
    return readings.mfu(run)
