"""Forward operations of the scored items per second of the traced window, over one chip's peak, in %."""
from chipbench import readings


def read(run):
    return readings.step_mfu(run)
