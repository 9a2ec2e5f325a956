"""Run one cell of the chip benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic and
its metrics are found by name from ``BENCHMARK.json``.  Set-up (import,
weights, the schedule search, the item pool, compile or compile-cache load
and warm-up) is timed as ``setup_s``; then the window is measured for
``--seconds``; then the window's scores are checked against the plain
reference.  With ``--trace 1`` the window runs under the profiler and the
result carries the per-layer metrics instead of the end-to-end ones.

The numbers compared for ``correct`` are printed, each beside its limit,
as the last lines of standard error; the last line of standard output is
the JSON result.  Exits non-zero, with no result, unless JAX finds a TPU
with as many chips as the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    cell = harness.resolve(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, log=log)
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
