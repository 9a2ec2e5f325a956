"""Padding slots over launched slots, in % (the fusion layer's waste)."""
from chipbench import readings


def read(run):
    return readings.pad_share(run)
