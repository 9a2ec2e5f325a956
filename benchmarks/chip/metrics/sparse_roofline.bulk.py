"""Least time of the sparse layer's bytes at peak HBM bandwidth over its device time, in %."""
from chipbench import readings


def read(run):
    return readings.sparse_roofline(run)
