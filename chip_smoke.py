"""Smoke run on one TPU chip: DLRM-RMC1 served at full width.

Drives the repo's serving entry point (``examples/serve_recsys.py::serve``)
once: RMC1 at the paper's accelerator scale (``prod=False``: 10 tables of
1M x 32 rows, pooling 80) with random weights from the seed, its schedule
searched for the ``T11-v5e`` server, and a few dozen open-loop seeded
queries served in fused launches on the chip.  The scores of the last full
fused batch are then checked against a plain float32 reference of the same
forward on the host's CPU device, at ``default_matmul_precision("highest")``.

Fails (non-zero exit, no result line) unless JAX's default backend is a
TPU.  The last line of standard output is the JSON result.

Run from the repo root:  python chip_smoke.py
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
MODEL, SERVER, QUERIES, SEED, QPS = "dlrm-rmc1", "T11-v5e", 32, 0, 20.0
# TPU float32 matmuls run as bfloat16 passes at the default precision, so
# the chip's scores differ from the float32 reference: each score must be
# within TOL * (1 + |reference|).
TOL = 2e-2


def main() -> int:
    import jax
    import numpy as np

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is {backend!r}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    from repro.common.compile_cache import setup_compile_cache
    from repro.configs.paper_models import PAPER_MODELS
    from repro.launch.steps import RECSYS_APPLY
    from serve_recsys import serve

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {setup_compile_cache()}")
    r = serve(MODEL, SERVER, QUERIES, SEED, qps=QPS)
    print(f"model: {r.model} (prod=False)")
    print(f"schedule for {r.server}: plan={r.plan} d={r.d} m={r.m} o={r.o}")
    print(f"served: {r.queries} queries, {r.items} items, {r.launches} launches")
    print(f"compile seconds: {r.compile_s:.3f}")

    cfg = PAPER_MODELS[MODEL](prod=False)
    apply = RECSYS_APPLY[cfg.interaction]
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, b: apply(p, b, cfg))(
            jax.device_put(r.params, cpu), jax.device_put(r.last_batch, cpu))
    ref = np.asarray(ref, np.float64)
    got = r.last_scores.astype(np.float64)
    if got.shape != (r.d,) or not np.all(np.isfinite(got)):
        raise SystemExit(f"bad scores: shape {got.shape}, "
                         f"finite {np.isfinite(got).all()}")
    diff = np.abs(got - ref)
    worst = float((diff / (TOL * (1.0 + np.abs(ref)))).max())
    print(f"max |chip - float32 reference| over {r.d} scores: {diff.max():.6g} "
          f"(max |reference| {np.abs(ref).max():.6g}; allowed "
          f"{TOL} * (1 + |reference|); worst score at {worst:.3f} of it)")
    print(f"smoke timing, not a metric: host-clock latency of {r.queries} "
          f"queries p50={np.percentile(r.latency_ms, 50):.3f}ms "
          f"p99={np.percentile(r.latency_ms, 99):.3f}ms")
    if worst > 1.0:
        raise SystemExit("scores outside the stated tolerance")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
