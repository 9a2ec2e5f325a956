"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, all on the host clock: the
scheduler, search and cluster simulator, none of them a device metric (the
chip benchmark is ``benchmarks/chip``).  The dry-run cells come from
``repro.launch.dryrun``, which needs a fresh 512-device process each; this
aggregator summarizes their cached artifacts instead of re-lowering.
"""
from __future__ import annotations

import pathlib

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts"


def _summarize_artifacts() -> None:
    dd = ART / "dryrun"
    if dd.exists():
        cells = sorted(dd.glob("*.json"))
        ok = len(cells)
        per_mesh = {}
        for c in cells:
            mesh = c.stem.split("__")[-1]
            per_mesh[mesh] = per_mesh.get(mesh, 0) + 1
        print(f"dryrun_cells,0.00,compiled={ok};" +
              ";".join(f"{k}={v}" for k, v in sorted(per_mesh.items())))


def main() -> None:
    print("name,us_per_call,derived")
    _summarize_artifacts()

    from benchmarks import (
        bench_accel_scheduling,
        bench_cluster,
        bench_gradient_search,
        bench_host_scheduling,
        bench_server_explore,
        bench_task_scheduler,
    )

    bench_host_scheduling.run()
    bench_accel_scheduling.run()
    bench_gradient_search.run()
    bench_server_explore.run()
    bench_task_scheduler.run()
    bench_cluster.run()


if __name__ == "__main__":
    main()
