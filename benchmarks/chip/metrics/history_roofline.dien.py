"""Least time of a launch's history gather bytes (reference ``work``'s ``history_bytes`` over the window's launches) at peak HBM bandwidth over the device time a traced launch spends under the ``sparse`` scope, in %."""
from chipbench import readings


def read(run):
    ms = readings.scope_ms(run, "sparse")
    if ms is None or not run.launches:
        return None
    least = readings.work(run)["history_bytes"] / run.launches / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * ms)
