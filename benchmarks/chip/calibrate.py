"""Readings that set the benchmark's limits and rates, on the chip.

    python3 benchmarks/chip/calibrate.py correct --workload <name> \
        --seconds <s> --seeds <n> ... [--control-seeds <n> ...]
    python3 benchmarks/chip/calibrate.py sweep --workload <name> \
        --seconds <s> --seed <n> --rates <q/s> ...

``correct`` runs the cell once per seed in one process and prints, for
each, the widest score gap of the program against the reference at the
stated precision (the lower reading of ``score_gap_limit``) and, for
information, against a ``highest``-precision reference.  With
``--control-seeds`` it runs the control (the reference in bfloat16 in the
program's place) on those seeds: its gaps are the upper reading.

``sweep`` serves the cell's open-loop traffic at each rate and prints the
p99, how late queries were issued, and whether the backlog grew (the mean
latency of the window's last quarter of queries over its first quarter).

Each reading is one JSON line on standard output, also appended to
``chiprun_out/calibrate.jsonl``.
"""
import argparse
import copy
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
OUT = HERE.parents[1] / "chiprun_out" / "calibrate.jsonl"


def emit(rec: dict):
    line = json.dumps(rec)
    print(line, flush=True)
    OUT.parent.mkdir(parents=True, exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("correct", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py needs a TPU", file=sys.stderr)
        return 1
    cell = harness.resolve(args.workload)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if args.mode == "correct":
        runs = [(s, None) for s in args.seeds]
        runs += [(s, "control_bfloat16") for s in args.control_seeds]
        for seed, fault in runs:
            t = time.perf_counter()
            res = harness.run(cell, seed, args.seconds, False, t, fault=fault,
                              log=log, also_against=("highest",))
            emit({"mode": "correct", "workload": cell.name, "seed": seed,
                  "run": fault or "program", "correct": res["correct"],
                  "score_gap": res["compared"]["score_gap"]["value"],
                  "gap_vs_highest": res["readings"]["highest"],
                  "items_compared": res["items_compared"],
                  "metrics": res["metrics"], "device": res["device"]})
        return 0

    for rate in args.rates:
        swept = copy.copy(cell)
        swept.mix = dict(cell.mix, rate_qps=rate)
        captured = {}
        res = harness.run(swept, args.seed, args.seconds, False,
                          time.perf_counter(), log=log, on_window=captured.update)
        lat = np.asarray(captured["latency_s"])
        q = max(1, len(lat) // 4)
        emit({"mode": "sweep", "workload": cell.name, "rate_qps": rate,
              "seed": args.seed, "queries": int(len(lat)),
              "p99_ms": res["metrics"].get("p99_ms", {}).get("value"),
              "p50_ms": float(np.percentile(lat, 50) * 1e3),
              "late_p99_ms": float(np.percentile(captured["late_s"], 99) * 1e3),
              "backlog_growth": float(lat[-q:].mean() / lat[:q].mean()),
              "window_s": captured["window_s"], "correct": res["correct"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
