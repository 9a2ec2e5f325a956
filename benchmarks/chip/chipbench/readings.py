"""What the metric readers compute from a run (``harness.Run``).

Each reader under ``metrics/`` calls one of these.  A reading that has
nothing to read (no trace, no open-loop latencies, no ops of a layer or a
scope) returns None, and the metric is left out of the result line.
"""
from __future__ import annotations

import numpy as np

HOST_INPUT = ("assemble", "device_put", "dispatch")


def percentile_ms(values, q: float) -> float | None:
    if values is None or len(values) == 0:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q)) * 1e3
    return v if np.isfinite(v) else None


def items_per_s(run) -> float | None:
    if run.window["latency_s"] is not None:
        return None
    return run.items / run.window["window_s"]


def pad_share(run) -> float | None:
    slots = run.launches * run.d
    return 100.0 * (slots - run.items) / slots if slots else None


def host_ms(run) -> float | None:
    if not run.launches:
        return None
    return 1e3 * sum(run.spans.get(k, 0.0) for k in HOST_INPUT) / run.launches


def idle_share(run) -> float | None:
    return None if run.trace is None else 100.0 * run.trace.idle_share


def device_ms(run) -> float | None:
    t = run.trace
    if t is None or not t.launches:
        return None
    return 1e3 * t.busy_s / t.launches


def _ms_a_launch(t, seconds: float | None) -> float | None:
    return 1e3 * seconds / t.launches if seconds and t.launches else None


def layer_ms(run, layer: str) -> float | None:
    """Device ms a launch of the ops attributed to ``layer`` by source file
    (the configuration's ``layers``)."""
    t = run.trace
    return None if t is None else _ms_a_launch(t, t.layer_s.get(layer))


def scope_ms(run, path: str) -> float | None:
    """Device ms a launch of the ops under the scope path ``path``
    (``sparse/gather``; names from the configuration's ``scopes``)."""
    t = run.trace
    return None if t is None else _ms_a_launch(t, t.scope_s.get(path))


def work(run) -> dict:
    """Least operations and bytes of the window's launches (the
    configuration family's reference, ``harness.reference``)."""
    from chipbench import harness

    return harness.reference(run.cell.cfg).work(
        run.cell.cfg, run.items, run.valid_lookups, run.launches)


def sparse_roofline(run) -> float | None:
    t = run.trace
    if t is None or not t.layer_s.get("sparse"):
        return None
    least = work(run)["sparse_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.layer_s["sparse"]


def dense_bound(run) -> tuple[str, float]:
    """(which bound, least seconds) of the dense layer's work."""
    w = work(run)
    flops_s = w["dense_flops"] / run.peaks["flops_per_s"]
    bytes_s = w["dense_bytes"] / run.peaks["hbm_bytes_per_s"]
    return ("flops", flops_s) if flops_s >= bytes_s else ("bytes", bytes_s)


def dense_roofline(run) -> float | None:
    t = run.trace
    if t is None or not t.layer_s.get("dense"):
        return None
    return 100.0 * dense_bound(run)[1] / t.layer_s["dense"]


def step_mfu(run) -> float | None:
    """Forward operations of the scored items per second of the traced
    window, over one chip's peak."""
    t = run.trace
    if t is None:
        return None
    return 100.0 * work(run)["flops"] / t.window_s / run.peaks["flops_per_s"]
