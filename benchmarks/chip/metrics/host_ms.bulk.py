"""Host input path per launch, in ms: assemble + device_put + dispatch, on the host clock."""
from chipbench import readings


def read(run):
    return readings.host_ms(run)
