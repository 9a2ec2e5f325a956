"""99th percentile, in ms, of how late each query was issued past its due time (queueing behind earlier queries included)."""
from chipbench import readings


def read(run):
    return readings.percentile_ms(run.window['late_s'], 99)
