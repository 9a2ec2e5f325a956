"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file, the traffic file under ``traffic/`` and one reader per
metric under ``metrics/``.  The program under test supplies the schedule
search, the weights and the jitted forward; the benchmark supplies the
inputs, the serving loop (the program has no request-taking server yet),
the spans, the trace reduction and the reference check.

What differs between model families is found by the configuration file's
``family`` key, in two modules of that name:

- ``chipbench/families/<family>.py``: ``INPUTS``, the step's input arrays
  by name, each with its value in a launch's padded slots; ``IDS``, those
  that hold ids (the ``sparse_dropped`` fault empties them);
  ``TRAFFIC_KEYS``, the traffic parameters its pool reads beyond
  ``traffic.Distributions`` (any other is refused);
  ``program_config(cfg)``, the program's configuration, checked against
  the file; ``program(pcfg)``, the program's ``(init(key),
  apply(params, batch))``; ``make_pool(seed, n, cfg, dist)``, ``n`` items
  (``traffic.Pool``) drawn from the seed;
- ``reference/<family>.py``: ``init(seed, cfg)``, the reference's own
  weights; ``forward_fn(cfg, precision)(params, batch)``, its scores
  (``precision`` the file's ``check.reference_precision``, or
  ``"bfloat16"`` for the control); ``work(cfg, items, valid_lookups,
  launches)``, the least operations and bytes of the window's work, read
  only by the readers that call ``readings.work``: ``flops``, the whole
  forward's operations (``step_mfu``), and whatever a family's roofline
  readers read (DLRM's ``sparse_bytes``, ``dense_flops``,
  ``dense_bytes``).

The configuration file also names the program's scopes (``scopes``) and
the source files of its layers (``layers``), by which the trace is read.

The serving loop mirrors ``examples/serve_recsys.py::serve``: a query's
items are split into launches of ``d`` (the schedule's fused batch), the
last one padded, and each launch is put on the device, run and waited on
before its scores come back.  Host spans (``assemble``, ``device_put``,
``dispatch``, ``wait``, ``readback``, ``until_due``) are timed on the host
clock and written into the profiler's trace as annotations.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time

import jax
import numpy as np

from chipbench import readings, scopes, traffic, tracing

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
# Items are reused round a pool of 16 full launches: no layer of the served
# path caches rows or results, so reuse changes nothing the device does,
# and drawing the pool stays about a second of set-up.
POOL_LAUNCHES = 16
SAMPLE_ITEMS = 16384    # items compared with the reference per run
DRAIN_S = 60.0          # queries not done this long after the window are failed
FAULTS = ("answer_altered", "half_batch", "sparse_dropped", "control_bfloat16")
STEP_MODULE = "jit_serve_step"  # the compiled step's program in the trace


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict            # the configuration file
    mix: dict            # the traffic file
    end_to_end: list     # BENCHMARK.json entries this cell reports
    per_layer: list


def resolve(workload: str, bench_file: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    cfg = load_json(bench_file.parent / files[w["config"]])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        return workload in m["workloads"] if "workloads" in m else None

    e2e = [m for m in spec["end_to_end"] if applies(m) is not False]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if applies(m) or (applies(m) is None and m["moves"] in names)]
    return Cell(workload, w["chips"], cfg, mix, e2e, layer)


def family(cfg: dict):
    """The configuration's family module, ``chipbench/families/<family>.py``."""
    return importlib.import_module(f"chipbench.families.{cfg['family']}")


def reference(cfg: dict):
    """The configuration's plain reference, ``reference/<family>.py``."""
    return importlib.import_module(f"reference.{cfg['family']}")


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


class Spans:
    """Seconds per span name on the host clock, and a profiler annotation
    around each span so that the trace shows it on the same clock."""

    def __init__(self):
        self._annotate = jax.profiler.TraceAnnotation
        self.total: dict[str, float] = collections.defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with self._annotate(name):
            yield
        self.total[name] += time.perf_counter() - t


# ---------------------------------------------------------------------------
# The served path
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Launch:
    start: int            # first pool item
    n: int                # real items; the rest of the launch is padding
    scores: np.ndarray    # [n] float32 as read back
    t0: float             # host clock at its start, and its seconds
    seconds: float


def padded(inputs: dict, arrays: dict[str, np.ndarray], d: int) -> dict[str, np.ndarray]:
    """A launch's batch of ``d`` slots: each input's rows, then its fill."""
    out = {}
    for k, fill in inputs.items():
        a = arrays[k]
        out[k] = np.full((d,) + a.shape[1:], fill, a.dtype)
        out[k][:len(a)] = a
    return out


class Server:
    """Fused launches of ``d`` pool items through the program's step; the
    family module (``fam``) names the step's inputs and their fill."""

    def __init__(self, step, params, pool: traffic.Pool, d: int, spans: Spans,
                 fam, fault: str | None = None):
        self.step, self.params, self.pool, self.d = step, params, pool, d
        self.spans, self.fam, self.fault = spans, fam, fault
        # the launch's buffers, all fill until a launch copies items in
        self.buf = padded(fam.INPUTS, {k: a[:0] for k, a in pool.arrays.items()}, d)
        self.launches: list[Launch] = []
        self.valid_lookups = 0
        self.items = 0

    def _fill(self, start: int, n: int):
        N = len(self.pool)
        s = start % N
        first = min(n, N - s)
        for k, fill in self.fam.INPUTS.items():
            buf, src = self.buf[k], self.pool.arrays[k]
            buf[:first] = src[s:s + first]
            if first < n:
                buf[first:n] = src[:n - first]
            if n < self.d:
                buf[n:] = fill
        if self.fault == "sparse_dropped":
            for k in self.fam.IDS:
                self.buf[k][:] = self.fam.INPUTS[k]

    def launch(self, start: int, n: int) -> np.ndarray:
        span = self.spans
        t0 = time.perf_counter()
        with span("assemble"):
            self._fill(start, n)
            batch = dict(self.buf)
        with span("device_put"):
            batch = jax.device_put(batch)
        with span("dispatch"):
            out = self.step(self.params, batch)
        with span("wait"):
            out.block_until_ready()
        with span("readback"):
            scores = np.array(out)[:n]
        if self.fault == "answer_altered":
            scores[0] += 1.0
        elif self.fault == "half_batch" and n > 1:
            half = (n + 1) // 2
            scores[half:] = scores[:n - half]
        self.launches.append(Launch(start, n, scores, t0, time.perf_counter() - t0))
        self.items += n
        self.valid_lookups += self.pool.lookups(start, n)
        return scores


def run_bulk(server: Server, seconds: float) -> dict:
    """Back-to-back full launches until ``seconds`` have passed."""
    d, cursor = server.d, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with server.spans("window"):
        while time.perf_counter() < deadline:
            server.launch(cursor, d)
            cursor += d
        t1 = time.perf_counter()
    n = len(server.launches)
    return {"window_s": t1 - t0, "attempted": n, "failed": 0,
            "queries": [[i] for i in range(n)], "latency_s": None, "late_s": None}


def run_open_loop(server: Server, due: np.ndarray, sizes: np.ndarray) -> dict:
    """Each query at its due time (or as soon as the one before is done),
    its items in ``ceil(q / d)`` launches, the last one padded."""
    spans, d = server.spans, server.d
    n = len(due)
    lat = np.full(n, np.inf)
    late = np.full(n, np.inf)
    queries = []
    cursor = 0
    t0 = time.perf_counter()
    stop = t0 + float(due[-1]) + DRAIN_S
    with spans("window"):
        for i in range(n):
            at = t0 + float(due[i])
            now = time.perf_counter()
            if now >= stop:
                break
            if now < at:
                with spans("until_due"):
                    time.sleep(at - now)
            late[i] = time.perf_counter() - at
            first = len(server.launches)
            q = int(sizes[i])
            for off in range(0, q, d):
                server.launch(cursor + off, min(d, q - off))
            cursor += q
            lat[i] = time.perf_counter() - at
            queries.append(list(range(first, len(server.launches))))
        t1 = time.perf_counter()
    failed = int(np.sum(~np.isfinite(lat)))
    return {"window_s": t1 - t0, "attempted": n, "failed": failed,
            "queries": queries, "latency_s": lat, "late_s": late}


# ---------------------------------------------------------------------------
# Correctness: the window's own scores against the plain reference
# ---------------------------------------------------------------------------


def sample_launches(window: dict, server: Server, seed: int) -> list[int]:
    """Launches to check: for open-loop traffic the longest query and then
    queries drawn from the seed; for bulk, launches drawn from the seed;
    in all, about SAMPLE_ITEMS items."""
    rng = np.random.default_rng([seed, 3])
    queries = window["queries"]
    order = list(rng.permutation(len(queries)))
    if window["latency_s"] is not None:
        longest = max(range(len(queries)),
                      key=lambda q: sum(server.launches[i].n for i in queries[q]))
        order.remove(longest)
        order.insert(0, longest)
    picked, items = [], 0
    for q in order:
        if items >= SAMPLE_ITEMS:
            break
        picked += queries[q]
        items += sum(server.launches[i].n for i in queries[q])
    return sorted(picked)


def check(cell: Cell, seed: int, server: Server, picked: list[int],
          precision: str | None = None) -> dict:
    """Reference scores of the picked launches' items, and the widest gap
    |program - reference| / (1 + |reference|) over them."""
    cfg = cell.cfg
    ref = reference(cfg)
    precision = precision or cfg["check"]["reference_precision"]
    params = ref.init(seed, cfg)
    fwd = ref.forward_fn(cfg, precision)
    inputs, arrays = server.fam.INPUTS, server.pool.arrays
    N, d = len(server.pool), server.d
    got, want = [], []
    for i in picked:
        la = server.launches[i]
        idx = (la.start + np.arange(la.n)) % N
        batch = padded(inputs, {k: arrays[k][idx] for k in inputs}, d)
        want.append(np.asarray(fwd(params, batch))[:la.n])
        got.append(la.scores)
    got = np.concatenate(got).astype(np.float64)
    want = np.concatenate(want).astype(np.float64)
    finite = np.isfinite(got)
    gap = float(np.max(np.abs(got - want) / (1.0 + np.abs(want)))) if finite.all() else math.inf
    return {"score_gap": gap, "nonfinite": int((~finite).sum()),
            "items_compared": int(got.size)}


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------


def schedule(cfg: dict, pcfg) -> tuple[dict, float]:
    """The offline stage: Hercules's schedule for this model on the server."""
    from repro.configs.paper_models import paper_profile
    from repro.core.devices import SERVER_TYPES
    from repro.core.gradient_search import gradient_search
    from repro.data.clicklog import ClickLogGenerator

    prog = cfg["program"]
    t = time.perf_counter()
    sizes = ClickLogGenerator(pcfg, seed=prog["search_seed"]).query_sizes(300)
    res = gradient_search(paper_profile(prog["model"], prod=prog["prod"]),
                          SERVER_TYPES[prog["server"]], sizes, o_grid=(1, 2))
    search_s = time.perf_counter() - t
    return ({"plan": res.placement.plan, "d": int(res.sched.batch),
             "m": int(res.sched.m), "o": int(res.sched.o)}, search_s)


def build(fam, pcfg, seed: int, d: int, pool: traffic.Pool):
    """Weights from the seed in one jitted call, and the step compiled at
    its one shape [d, ...]."""
    init, apply = fam.program(pcfg)
    params = jax.jit(init)(jax.random.PRNGKey(seed % 2**32))

    def serve_step(p, b):
        return apply(p, b)

    batch = {k: pool.arrays[k][:d] for k in fam.INPUTS}
    return params, jax.jit(serve_step).lower(params, batch).compile()


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    search_s: float
    window: dict
    spans: dict[str, float]
    launches: int
    items: int
    valid_lookups: int
    d: int
    peaks: dict | None = None
    trace: tracing.Reduced | None = None


def device_info() -> dict:
    """The devices as JAX reports them, with the peak memory of the fullest
    (0 where the backend keeps no memory statistics)."""
    dev = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in dev)
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peak rates for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Counts backend compilations while active (there should be none in
    the window).  One listener per process, registered on first use."""

    n, active, _registered = 0, False, False

    @classmethod
    def start(cls):
        if not cls._registered:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True
        cls.n, cls.active = 0, True

    @classmethod
    def _on(cls, event: str, *_args, **_kw):
        if cls.active and event == "/jax/core/compile/backend_compile_duration":
            cls.n += 1


def compile_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it."""
    from repro.common.compile_cache import setup_compile_cache

    return setup_compile_cache()


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        fault: str | None = None, log=print, also_against=(), on_window=None) -> dict:
    """Set up, measure, check.  Returns the result line's object.
    ``also_against`` names further reference precisions whose widest gap
    is reported (under ``readings``) but not judged; ``on_window`` is
    called with the window's record (latencies and all)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    compile_cache()
    fam = family(cell.cfg)
    pcfg = fam.program_config(cell.cfg)
    sched, search_s = schedule(cell.cfg, pcfg)
    d = sched["d"]
    dist = traffic.Distributions.from_mix(cell.mix, fam.TRAFFIC_KEYS)
    pool = fam.make_pool(seed, POOL_LAUNCHES * d, cell.cfg, dist)
    params, step = build(fam, pcfg, seed, d, pool)
    if fault == "control_bfloat16":
        ref = reference(cell.cfg)
        params = ref.init(seed, cell.cfg)
        fwd = ref.forward_fn(cell.cfg, "bfloat16")

        def step(p, b):  # the control in the program's place
            return fwd(p, b).astype(np.float32)

    spans = Spans()
    server = Server(step, params, pool, d, spans, fam, fault)
    for _ in range(2):  # warm the whole path at its one shape
        server.launch(0, d)
    open_loop = cell.mix["kind"] == "open_loop"
    if open_loop:
        rate = float(cell.mix["rate_qps"])
        due, sizes = traffic.open_loop(seed, max(1, round(rate * seconds)), rate, dist)
    server.launches.clear()
    server.items = server.valid_lookups = 0
    spans.total.clear()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start

    CompileCounter.start()
    if trace_dir:  # device ops and the benchmark's spans; no Python tracer
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = (run_open_loop(server, due, sizes) if open_loop
                  else run_bulk(server, seconds))
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
        CompileCounter.active = False
    if on_window:
        on_window(window)
    device = device_info()
    log(f"schedule: plan={sched['plan']} d={d} m={sched['m']} o={sched['o']}; "
        f"search {search_s:.3f} s; setup {setup_s:.3f} s; "
        f"launches {len(server.launches)} items {server.items}; "
        f"compiles in window {CompileCounter.n}; peak bytes {device['memory_peak_bytes']}")

    slowest = sorted(server.launches, key=lambda la: -la.seconds)[:3]
    t_window = server.launches[0].t0 if server.launches else 0.0
    log("slowest launches: " + ", ".join(
        f"{1e3 * la.seconds:.3f} ms at {la.t0 - t_window:.3f} s" for la in slowest))

    r = Run(cell=cell, setup_s=setup_s, search_s=search_s, window=window,
            spans=dict(spans.total), launches=len(server.launches),
            items=server.items, valid_lookups=server.valid_lookups, d=d)
    result = {"correct": None, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": {}, "device": device}
    if trace_dir:
        r.peaks = peaks_for(device["kind"])
        try:
            r.trace = read_trace(trace_dir, step, cell.cfg)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {r.trace.launches} step runs in {r.trace.window_s:.6f} s, "
            f"busy {r.trace.busy_s:.6f} s, device s by layer {r.trace.layer_s}, "
            f"by scope {r.trace.scope_s}")
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in r.trace.top_ops],
            "idle_gaps": sorted(([n, s] for n, s in r.trace.idle_by_span.items()),
                                key=lambda x: -x[1])[:10],
        }
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(r)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    # correctness, once the window has closed and its state is freed
    picked = sample_launches(window, server, seed)
    del params, step
    server.params = server.step = None
    got = check(cell, seed, server, picked)
    limit = float(cell.cfg["check"]["score_gap_limit"])
    compared = {
        "score_gap": {"value": got["score_gap"], "limit": limit},
        "nonfinite_scores": {"value": got["nonfinite"], "limit": 0},
        "failed_requests": {"value": window["failed"], "limit": 0},
    }
    result["correct"] = bool(got["score_gap"] <= limit and got["nonfinite"] == 0
                             and window["failed"] == 0)
    result["items_compared"] = got["items_compared"]
    if also_against:
        result["readings"] = {p: check(cell, seed, server, picked, p)["score_gap"]
                              for p in also_against}
    result["compared"] = compared
    return result


def read_trace(trace_dir: str, step, cfg: dict) -> tracing.Reduced:
    """The traced window, reduced (see ``tracing``), with device seconds by
    the scope paths of the configuration's ``scopes`` (see ``scopes``)."""
    data = jax.profiler.ProfileData.from_file(tracing.find_xplane(trace_dir))
    ops, modules, spans = tracing.events(data)
    text = step.as_text() or ""
    reduced = tracing.reduce(ops, modules, spans, tracing.op_layers(text, cfg["layers"]),
                             step_module=STEP_MODULE)
    reduced.scope_s = scopes.scope_seconds(ops, spans, scopes.op_scopes(text, cfg["scopes"]))
    return reduced
