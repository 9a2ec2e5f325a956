"""Run one cell once, with readings that the benchmark's result line lacks.

    python3 benchmarks/chip/probe.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The run is ``run.py``'s own (``harness.run``: the same set-up, window and
check).  Besides its result, the JSON line on standard output holds, under
``probe``:

- ``items_per_s``, traced or not, so that traced and untraced windows can
  be compared (what the tracer costs);
- ``slowest``: the window's three slowest launches, each with its start in
  the window and its seconds by host span (``assemble``, ``device_put``,
  ``dispatch``, ``wait``, ``readback``), to place a stall;
- with ``--trace 1``: ``scope_s`` and ``scope_ms``, device seconds in the
  window and ms a launch by every scope path of the configuration's
  ``scopes`` (``harness.read_trace``; ``""`` is no scope), and ``idle_s``,
  the idle seconds inside step runs and between them.

Exits non-zero, with no result, unless JAX finds a TPU with the cell's
chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


class LaunchSpans:
    """The harness's spans, also kept launch by launch: a launch opens with
    ``assemble``, and the window's start forgets the warm-up launches."""

    def __init__(self, spans):
        self.spans = spans
        self.launches: list[dict[str, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if name == "window":
            self.launches.clear()
        elif name == "assemble":
            self.launches.append({})
        t = time.perf_counter()
        with self.spans(name):
            yield
        if name != "window" and self.launches:
            self.launches[-1][name] = time.perf_counter() - t


def probe(cell, seed: int, seconds: float, trace: bool, t_start: float,
          log=print) -> dict:
    """``harness.run`` with the readings above added under ``probe``."""
    import jax

    from chipbench import harness, scopes, tracing

    seen: dict = {}
    plain_server, plain_read_trace = harness.Server, harness.read_trace

    class Server(harness.Server):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.spans = LaunchSpans(self.spans)
            seen["server"] = self

    def read_trace(trace_dir, step, cfg):
        reduced = plain_read_trace(trace_dir, step, cfg)
        data = jax.profiler.ProfileData.from_file(tracing.find_xplane(trace_dir))
        ops, modules, spans = tracing.events(data)
        seen["scope_s"] = reduced.scope_s
        seen["idle_s"] = scopes.idle_split(ops, modules, spans, harness.STEP_MODULE)
        seen["launches"] = reduced.launches
        return reduced

    harness.Server, harness.read_trace = Server, read_trace
    try:
        result = harness.run(cell, seed, seconds, trace, t_start, log=log,
                             on_window=lambda w: seen.update(window_s=w["window_s"]))
    finally:
        harness.Server, harness.read_trace = plain_server, plain_read_trace

    server = seen["server"]
    t0 = server.launches[0].t0 if server.launches else 0.0
    timed = zip(server.launches, server.spans.launches)
    slowest = sorted(timed, key=lambda x: -x[0].seconds)[:3]
    extra = {
        "items_per_s": server.items / seen["window_s"],
        "slowest": [{"at_s": la.t0 - t0, "seconds": la.seconds, "spans": sp}
                    for la, sp in slowest],
    }
    log("slowest launches by span: " + "; ".join(
        f"{1e3 * s['seconds']:.3f} ms at {s['at_s']:.3f} s ("
        + ", ".join(f"{k} {1e3 * v:.3f}" for k, v in s["spans"].items()) + ")"
        for s in extra["slowest"]))
    if "scope_s" in seen:
        n = seen["launches"]
        extra["scope_s"] = seen["scope_s"]
        extra["scope_ms"] = {p: 1e3 * s / n for p, s in seen["scope_s"].items()} if n else {}
        extra["idle_s"] = seen["idle_s"]
        log(f"device s by scope {extra['scope_s']}; idle s {extra['idle_s']}")
    result["probe"] = extra
    return result


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
        print(f"probe.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = probe(cell, args.seed, args.seconds, bool(args.trace), T_START, log=log)
    result = {"workload": args.workload, "seed": args.seed,
              "trace": args.trace, **result}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
