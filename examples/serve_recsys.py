"""Serving driver: a Table I model served through the Hercules-chosen task
schedule, with the query router in front.

The offline stage searches the schedule (plan, d, m, o) for the model on a
named server type (Algorithm 1, ``gradient_search``).  The online stage
turns open-loop, seeded Poisson arrivals into fused launches of the jitted
forward, each padded to ``d`` items and waited on, on whatever device JAX
runs on.  The model is the paper's accelerator scale (``prod=False``, the
Table I widths) with random weights drawn from the seed.

Run:  PYTHONPATH=src python examples/serve_recsys.py
          [--model dlrm-rmc1] [--server T2] [--queries 40] [--qps 60]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.common.compile_cache import setup_compile_cache
from repro.configs.paper_models import PAPER_MODELS, paper_profile
from repro.core.devices import SERVER_TYPES
from repro.core.gradient_search import gradient_search
from repro.data.clicklog import ClickLogGenerator
from repro.launch.steps import RECSYS_APPLY, RECSYS_INIT
from repro.serving.router import QueryRouter, ServerSlot


@dataclasses.dataclass
class ServeResult:
    model: str
    server: str
    plan: str
    d: int
    m: int
    o: int
    queries: int
    items: int
    launches: int
    compile_s: float
    latency_ms: np.ndarray  # per query: arrival to its last launch done
    params: dict            # the served weights
    last_batch: dict        # inputs of the last fused launch (padded to d)
    last_scores: np.ndarray  # that launch's scores, [d]


def serve(model: str, server: str, n_queries: int, seed: int = 0, *,
          qps: float = 60.0) -> ServeResult:
    """Serve ``n_queries`` seeded queries of ``model`` (``prod=False``)
    under the schedule searched for ``server`` (a ``SERVER_TYPES`` key)."""
    cfg = PAPER_MODELS[model](prod=False)
    params = RECSYS_INIT[cfg.interaction](jax.random.PRNGKey(seed), cfg)
    gen = ClickLogGenerator(cfg, seed=seed)

    # offline stage: the schedule for this workload on the named server
    res = gradient_search(paper_profile(model, prod=False),
                          SERVER_TYPES[server], gen.query_sizes(300),
                          o_grid=(1, 2))
    d = res.sched.batch
    router = QueryRouter([ServerSlot(server, res.qps)])

    apply = RECSYS_APPLY[cfg.interaction]
    warm = gen.batch(d, with_labels=False)
    t0 = time.perf_counter()
    step = jax.jit(lambda p, b: apply(p, b, cfg)).lower(params, warm).compile()
    compile_s = time.perf_counter() - t0

    # online stage: open-loop seeded Poisson arrivals; each query's items
    # fuse into launches of d (the last one padded), blocking on each
    sizes = gen.query_sizes(n_queries)
    arrivals = np.cumsum(np.random.default_rng(seed).exponential(
        1.0 / qps, n_queries))
    lat, launches = [], 0
    start = time.perf_counter()
    for q, t_arr in zip(sizes, arrivals):
        wait = start + t_arr - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        slot = router.pick()
        slot.inflight += 1
        for _ in range(0, q, d):
            batch = gen.batch(d, with_labels=False)  # fused launch (padded)
            scores = step(params, jax.device_put(batch)).block_until_ready()
            launches += 1
        slot.inflight -= 1
        dt = time.perf_counter() - (start + t_arr)
        router.observe_latency(dt)
        lat.append(dt)
    return ServeResult(
        model=model, server=server, plan=res.placement.plan, d=d,
        m=res.sched.m, o=res.sched.o, queries=len(lat),
        items=int(sizes.sum()), launches=launches, compile_s=compile_s,
        latency_ms=np.asarray(lat) * 1e3, params=params, last_batch=batch,
        last_scores=np.asarray(scores))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="dlrm-rmc1", choices=sorted(PAPER_MODELS))
    ap.add_argument("--server", default="T2", choices=sorted(SERVER_TYPES))
    ap.add_argument("--queries", type=int, default=40)
    ap.add_argument("--qps", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    setup_compile_cache()
    r = serve(args.model, args.server, args.queries, args.seed, qps=args.qps)
    print(f"hercules schedule for {r.model} on {r.server}: plan={r.plan} "
          f"d={r.d} m={r.m} o={r.o}")
    print(f"served {r.queries} queries ({r.items} items, {r.launches} "
          f"launches) on {jax.devices()[0].platform}; compile {r.compile_s:.2f}s")
    print(f"host-clock latency p50={np.percentile(r.latency_ms, 50):.1f}ms "
          f"p99={np.percentile(r.latency_ms, 99):.1f}ms")


if __name__ == "__main__":
    main()
