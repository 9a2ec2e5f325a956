"""Device busy time per launch, in ms: union of op intervals in the trace over step runs."""
from chipbench import readings


def read(run):
    return readings.device_ms(run)
