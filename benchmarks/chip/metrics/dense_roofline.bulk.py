"""Least time of the dense layer's work (the larger of its FLOP and byte bounds) over its device time, in %."""
from chipbench import readings


def read(run):
    return readings.dense_roofline(run)
