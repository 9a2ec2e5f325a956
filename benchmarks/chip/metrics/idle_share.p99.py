"""Share of the traced window, in %, in which no op ran on the device."""
from chipbench import readings


def read(run):
    return readings.idle_share(run)
