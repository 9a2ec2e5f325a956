"""Items scored in the window over the window's seconds (bulk traffic)."""
from chipbench import readings


def read(run):
    return readings.items_per_s(run)
