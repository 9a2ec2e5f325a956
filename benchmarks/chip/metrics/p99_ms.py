"""99th percentile, in ms, of every query of the window, from its due time to its last score on the host; a failed query counts as missing."""
from chipbench import readings


def read(run):
    return readings.percentile_ms(run.window['latency_s'], 99)
