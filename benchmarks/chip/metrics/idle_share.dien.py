"""Share of the traced window, in %, in which the device is idle, extrapolated from the traced launches: 1 - (device ms a traced launch x the window's launches) / the window.  The profiler keeps about 6M device events, and a DIEN launch has about 2,700 (its loops' bodies included), so a 51 s window is traced for its first launches only; the union of op intervals over the whole window (``idle_share.bulk``) would count the untraced rest as idle.  This assumes the untraced launches take as long on the device as the traced ones."""
from chipbench import readings


def read(run):
    ms = readings.device_ms(run)
    if ms is None:
        return None
    return 100.0 * (1.0 - 1e-3 * ms * run.launches / run.trace.window_s)
