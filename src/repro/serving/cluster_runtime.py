"""Fleet-scale, query-granular online serving (closing the paper's loop).

``provision_day`` (stage 2, `repro.core.cluster`) trusts the efficiency
table's QPS column: an interval is "served" if the LP covers the load with
profiled throughput numbers.  This module validates that claim the way
DeepRecSys and Hera do — by actually serving queries: it consumes the
allocations of a :class:`~repro.core.cluster.StatefulProvisioner` and
drives Poisson query streams through one
:class:`~repro.serving.router.QueryRouter` per workload, with per-server
behaviour reproduced from the PR-2 vectorized engine:

- each allocated server instance is a router slot backed by a
  :class:`PairService` — the (workload, server-type) pair's profiled
  optimal placement + scheduling config, whose sub-query splits and
  duration tables come from the shared :class:`~repro.serving.simulator.
  SimCache` (common random numbers across intervals, slots and policies);
- routing is the router's deterministic low-discrepancy weighted
  assignment; newly provisioned servers join the pool only after their
  model load completes, drained servers stop taking queries but finish
  in-flight work (make-before-break when ``drain_s >= model_load_s``);
- mid-day failures land *inside* the measured window: the victim's
  unfinished queries re-dispatch to healthy slots at the detection time,
  and the provisioner re-solves on the shrunken pool at the next interval;
- stragglers hedge once the router's p99-based threshold trips: the
  duplicate is admitted into the alternate slot's **live** queue at
  ``arrival + threshold`` and contends with that slot's in-flight work.

The simulation is **continuous-time across intervals**: each slot's pool
state (the per-server free times — its backlog of unfinished work) is
carried from one measured window into the next, through hysteresis holds,
make-before-break transitions, slot retirement and mid-window failures.
Measured windows therefore abut in queue time; a slot pushed past its
sustainable rate accumulates backlog day-long instead of being quietly
reset to an idle pool at every interval boundary — which is exactly the
regime (utilization → 1) where the paper's feasibility-frontier claims
are decided.  Per-interval latency/SLA series are exposed alongside the
day-level aggregate, and the achieved tail feeds back into the
provisioner's hysteresis decision (``StatefulProvisioner.step(load,
tail_ok=...)``).  See ``docs/cluster_serving.md``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core.cluster import (
    EfficiencyTable,
    StatefulProvisioner,
    TransitionConfig,
)
from repro.core.devices import SERVER_TYPES, DeviceProfile
from repro.core.partition import enumerate_placements
from repro.core.perfmodel import (
    accel_engine_time,
    accel_link_time,
    cpu_stage_time,
)
from repro.core.workload import ModelProfile
from repro.serving.engine import fifo_finish, fifo_finish_state
from repro.serving.event_core import merge_event_streams
from repro.serving.router import QueryRouter, ServerSlot
from repro.serving.simulator import (
    _PROBE_CAP,
    SchedConfig,
    SimCache,
    _accel_pipeline,
    _fusion_groups,
)


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Knobs of the query-granular day simulation."""

    queries_per_interval: int = 1500  # window cap per workload (CRN prefix)
    hedge_quantile: float = 0.99
    hedge_factor: float = 2.0
    sla_quantile: float = 0.95        # "meets SLA" = this quantile <= sla_ms
    # --- event core (exact full-interval mode, see event_core.py) ---------
    # event_core=True simulates every arrival of the interval (cap below,
    # not _PROBE_CAP), extends each measured window to the interval end so
    # nothing is bridged by stationarity, batches the per-slot k-server
    # pools through event_core.fleet_fifo_finish, and re-serves a hedge
    # target's own primaries event-ordered (their latencies reflect the
    # duplicate's admission instead of keeping first-pass values)
    event_core: bool = False
    event_core_queries: int = 200_000  # full-interval cap per (workload, t)
    # keep the raw per-(workload, interval) latency arrays on the result
    # (``DayResult.latencies``) — used by the geo layer to attribute spilled
    # queries to their origin region; off by default (event-core days can
    # measure 10^7+ queries)
    collect_latencies: bool = False


@dataclasses.dataclass(frozen=True)
class DayInputs:
    """Everything :func:`simulate_cluster_day` needs about *one* day.

    ``compile_scenario`` produces one of these (``CompiledScenario.inputs``);
    hand-rolled days construct it directly.  The bundle is the day's data —
    which policy serves it and with which runtime knobs stay call-site
    arguments (``simulate_cluster_day(inputs, policy=..., config=...)``), so
    the same inputs can be served under every policy for a CRN comparison.
    """

    table: EfficiencyTable
    records: dict[str, dict]
    profiles: dict[str, ModelProfile]
    traces: np.ndarray                  # [M, T] per-workload diurnal loads
    servers: dict[str, DeviceProfile] | None = None
    overprovision: float = 0.05
    transitions: TransitionConfig | None = None
    failures: list[tuple[int, int, float]] | None = None
    query_sizes: np.ndarray | None = None
    seed: int = 0
    # optional repro.core.colocation.ColocationTable: when set, the
    # provisioner may pack complementary tenants onto shared machines and
    # the runtime serves their per-tenant streams on one machine identity
    # with interference-dilated duration tables.  None (the default) keeps
    # the single-tenant day bitwise unchanged.
    colocation: object | None = None


@dataclasses.dataclass
class DayResult:
    """Typed result of :func:`simulate_cluster_day`.

    ``to_dict()`` reproduces the historical raw-dict shape bit-for-bit
    (``power`` -> ``"power_w"``, ``per_workload`` -> ``"workloads"``), so
    JSON baselines pinned against the old return value stay valid.
    """

    policy: str
    power: np.ndarray                   # [T] provisioned W incl. drain
    capacity: np.ndarray                # [T] machines allocated
    churn: np.ndarray                   # [T] machines added + removed
    feasible: bool
    peak_power_w: float
    avg_power_w: float
    peak_capacity: int
    avg_capacity: float
    resolves: int
    holds: int
    tail_resolves: int
    total_churn: int
    per_workload: dict[str, dict]       # day-level aggregates per workload
    series: dict                        # {"interval_s", "per_workload"}
    all_meet_sla: bool
    events: list[str]
    # raw per-(workload, interval) latency seconds; populated only under
    # RuntimeConfig(collect_latencies=True) and excluded from to_dict()
    latencies: list[list[np.ndarray | None]] | None = None
    # [T] shared (co-located) machines per interval; populated only when
    # the day ran with a colocation table and excluded from to_dict() so
    # pinned single-tenant baselines keep their exact key set
    co_capacity: np.ndarray | None = None

    def to_dict(self) -> dict:
        return {
            "policy": self.policy,
            "power_w": self.power,
            "capacity": self.capacity,
            "churn": self.churn,
            "feasible": self.feasible,
            "peak_power_w": self.peak_power_w,
            "avg_power_w": self.avg_power_w,
            "peak_capacity": self.peak_capacity,
            "avg_capacity": self.avg_capacity,
            "resolves": self.resolves,
            "holds": self.holds,
            "tail_resolves": self.tail_resolves,
            "total_churn": self.total_churn,
            "workloads": self.per_workload,
            "series": self.series,
            "all_meet_sla": self.all_meet_sla,
            "events": self.events,
        }


# ---------------------------------------------------------------------------
# per-slot pool state (the carried backlog)
# ---------------------------------------------------------------------------
#
# A slot's state is a dict of float arrays — one per internal pool resource
# (CPU thread pool, sparse/dense pools, accel host pool / co-location slots
# / link / engine), each entry a server's free time.  Between windows the
# state is stored *relative* to the window end (residual seconds of
# unfinished work); at the next window it is re-anchored at the interval
# start, so a drained slot re-enters idle and an overloaded one re-enters
# exactly as deep in backlog as it left.


def _state_abs(residual: dict[str, np.ndarray], t0: float) -> dict:
    """Anchor a residual (relative-seconds) state at absolute time ``t0``."""
    return {k: t0 + v for k, v in residual.items()}


def _state_residual(state: dict[str, np.ndarray], w_end: float) -> dict:
    """Convert an absolute end-of-window state to residual seconds."""
    return {k: np.maximum(v - w_end, 0.0) for k, v in state.items()}


def _drain_horizon(state: dict[str, np.ndarray], w_end: float) -> float:
    """Seconds past ``w_end`` until the slot is fully drained (0 = idle)."""
    if not state:
        return 0.0
    return max(max(float(v.max()) - w_end, 0.0) for v in state.values())


def _state_copy(state: dict[str, np.ndarray]) -> dict:
    return {k: v.copy() for k, v in state.items()}


# ---------------------------------------------------------------------------
# per-(workload, server-type) service model
# ---------------------------------------------------------------------------


class PairService:
    """Query-granular service model of one (workload, server-type) pair.

    Reproduces the single-server simulator's fast path on an arbitrary
    subset of the CRN query stream: the profiled optimal placement and
    scheduling config define the pool structure, the shared
    :class:`SimCache` supplies sub-query splits and duration tables, and
    the k-server FIFO recurrence / accel admission-link-engine pipeline
    come from :mod:`repro.serving.engine` and the simulator.  ``finish``
    on the full stream prefix is bit-identical to the engine's fast path
    (pinned by ``tests/test_cluster_runtime.py``); with a ``state`` it
    additionally starts from / hands back carried pool backlog.
    """

    def __init__(self, profile: ModelProfile, device: DeviceProfile,
                 record: dict, cache: SimCache, dilation: float = 1.0):
        self.profile = profile
        self.device = device
        self.cache = cache
        # interference dilation of a co-located tenant (>= 1): every pool
        # duration multiplies by it and the sustainable rate divides by it.
        # At exactly 1.0 no multiply runs, keeping the solo path bitwise.
        self.dilation = float(dilation)
        self.qps = float(record["qps"])
        if self.dilation != 1.0:
            self.qps = self.qps / self.dilation
        self.sched = SchedConfig(
            batch=int(record["d"]), m=int(record["m"]), o=int(record["o"]),
            sd_sparse=int(record["sd_sparse"]),
        )
        self.plan = record["plan"]
        placements = enumerate_placements(profile, device)
        by_plan = [p for p in placements if p.plan == self.plan]
        self.placement = by_plan[0] if by_plan else placements[0]
        d = max(self.sched.batch, 1)
        self.d = d
        sp = cache.tables.split(d)
        self.offsets = sp["offsets"]
        self.inv = sp["inv"]
        self.sub_s = sp["sub_s"]
        t, pl, s = cache.tables, self.placement, self.sched
        self.k = max(s.m, 1)
        if self.plan == "cpu_model":
            self.dur = t.cpu_durations(pl.host_ops, s.o, s.m, d, device)
            if self.dilation != 1.0:
                self.dur = self.dur * self.dilation
        elif self.plan == "cpu_sd":
            self.k_sparse = max(s.sd_sparse, 1)
            self.dur_sparse = t.cpu_durations(
                pl.host_sparse, s.o, self.k_sparse, d, device)
            self.dur_dense = t.cpu_durations(pl.host_dense, 1, s.m, d, device)
            if self.dilation != 1.0:
                self.dur_sparse = self.dur_sparse * self.dilation
                self.dur_dense = self.dur_dense * self.dilation
        else:
            self.host_threads = max(device.cpu.cores // max(s.o, 1), 1)

    # -- internals -----------------------------------------------------------

    def _sub_index(self, qidx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Sub-query indices (into the full CRN split) for queries ``qidx``."""
        starts = self.offsets[qidx]
        counts = (self.offsets[qidx + 1] - starts).astype(np.int64)
        total = int(counts.sum())
        if total == 0:
            return np.zeros(0, np.int64), counts
        cum0 = np.concatenate([[0], np.cumsum(counts)[:-1]])
        sub = np.repeat(starts - cum0, counts) + np.arange(total)
        return sub, counts

    def _scalar_table(self, key: tuple, fn, uniq: np.ndarray) -> np.ndarray:
        return self.cache.tables.scalar_vec(key, fn, uniq)

    def _accel(self, sub_ready: np.ndarray, sub_s: np.ndarray,
               state: dict | None = None) -> np.ndarray:
        """Fused launches through host pool -> admission -> link -> engine,
        identical to the simulator's ``_fast_accel`` structure."""
        pl, s, dev = self.placement, self.sched, self.device
        starts, totals = _fusion_groups(sub_ready, sub_s.astype(np.int64),
                                        self.d, s.fuse)
        bounds = np.append(starts, len(sub_ready))
        ready = sub_ready[bounds[1:] - 1]
        uniq_t, inv_t = np.unique(totals, return_inverse=True)
        o = max(s.o, 1)
        if pl.host_ops:
            th = self._scalar_table(
                ("cpu_stage", pl.host_ops, o, self.host_threads, dev.name),
                lambda b: cpu_stage_time(pl.host_ops, b, o, dev,
                                         self.host_threads), uniq_t)[inv_t]
            if self.dilation != 1.0:
                th = th * self.dilation
            if state is None:
                ready = fifo_finish(ready, th, self.host_threads)
            else:
                ready, state["host"] = fifo_finish_state(
                    ready, th, self.host_threads, state["host"])
        te = self._scalar_table(
            ("accel_engine", pl.accel_ops, dev.name),
            lambda b: accel_engine_time(pl.accel_ops, b, dev), uniq_t)[inv_t]
        tl = self._scalar_table(
            ("accel_link", pl.link_bytes_per_item, dev.name),
            lambda b: accel_link_time(pl.link_bytes_per_item, b, dev),
            uniq_t)[inv_t]
        if self.dilation != 1.0:
            te = te * self.dilation
            tl = tl * self.dilation
        if state is None:
            e_end = _accel_pipeline(ready, tl, te, s.m)
        else:
            e_end, (colo, link, eng) = _accel_pipeline(
                ready, tl, te, s.m, colo0=state["colo"],
                link0=float(state["link"][0]), eng0=float(state["eng"][0]),
                return_state=True)
            state["colo"] = colo
            state["link"] = np.array([link])
            state["eng"] = np.array([eng])
        return np.repeat(e_end, np.diff(bounds))

    # -- public --------------------------------------------------------------

    def fresh_state(self) -> dict[str, np.ndarray]:
        """Idle pool state (residual form: all zeros), shaped per plan."""
        if self.plan == "cpu_model":
            return {"pool": np.zeros(self.k)}
        if self.plan == "cpu_sd":
            return {"sparse": np.zeros(self.k_sparse),
                    "dense": np.zeros(self.k)}
        st = {"colo": np.zeros(self.k), "link": np.zeros(1),
              "eng": np.zeros(1)}
        if self.placement.host_ops:
            st["host"] = np.zeros(self.host_threads)
        return st

    def finish(self, qidx: np.ndarray, ready: np.ndarray,
               state: dict | None = None) -> np.ndarray:
        """Per-query finish times for CRN-stream queries ``qidx`` entering
        this server's pools at ``ready`` (sorted).  Without ``state`` the
        pools start idle (the historical, bit-pinned path); with a
        ``state`` dict (absolute free times, see :func:`_state_abs`) the
        pools start from the carried backlog and ``state`` is updated in
        place to the end-of-stream pool state."""
        qidx = np.asarray(qidx, np.int64)
        out = np.array(ready, dtype=np.float64, copy=True)
        if len(qidx) == 0:
            return out
        sub, counts = self._sub_index(qidx)
        nz = counts > 0
        if not nz.any():
            return out
        sub_ready = np.repeat(out, counts)
        inv = self.inv[sub]
        if self.plan == "cpu_model":
            if state is None:
                ends = fifo_finish(sub_ready, self.dur[inv], self.k)
            else:
                ends, state["pool"] = fifo_finish_state(
                    sub_ready, self.dur[inv], self.k, state["pool"])
        elif self.plan == "cpu_sd":
            if state is None:
                s_end = fifo_finish(sub_ready, self.dur_sparse[inv],
                                    self.k_sparse)
                ends = fifo_finish(s_end, self.dur_dense[inv], self.k)
            else:
                s_end, state["sparse"] = fifo_finish_state(
                    sub_ready, self.dur_sparse[inv], self.k_sparse,
                    state["sparse"])
                ends, state["dense"] = fifo_finish_state(
                    s_end, self.dur_dense[inv], self.k, state["dense"])
        else:
            ends = self._accel(sub_ready, self.sub_s[sub], state)
        _reduce_queries(out, ends, counts, nz)
        return out

    def solo_time(self, qidx: np.ndarray) -> np.ndarray:
        """Unloaded per-query service time (lower bound on any completion):
        list-scheduling wave bound ``max(longest sub-query, work / k)`` per
        pool stage; serialized link+engine on accelerators."""
        qidx = np.asarray(qidx, np.int64)
        sub, counts = self._sub_index(qidx)
        out = np.zeros(len(qidx))
        nz = counts > 0
        if not nz.any():
            return out
        cuts = np.concatenate([[0], np.cumsum(counts)])[:-1][nz]

        def wave(dur: np.ndarray, k: int) -> np.ndarray:
            longest = np.maximum.reduceat(dur, cuts)
            work = np.add.reduceat(dur, cuts)
            return np.maximum(longest, work / max(k, 1))

        inv = self.inv[sub]
        if self.plan == "cpu_model":
            out[nz] = wave(self.dur[inv], self.k)
        elif self.plan == "cpu_sd":
            out[nz] = wave(self.dur_sparse[inv], self.k_sparse) + \
                wave(self.dur_dense[inv], self.k)
        else:
            pl, dev = self.placement, self.device
            uniq, inv_s = np.unique(self.sub_s[sub], return_inverse=True)
            te = self._scalar_table(
                ("accel_engine", pl.accel_ops, dev.name),
                lambda b: accel_engine_time(pl.accel_ops, b, dev), uniq)
            tl = self._scalar_table(
                ("accel_link", pl.link_bytes_per_item, dev.name),
                lambda b: accel_link_time(pl.link_bytes_per_item, b, dev),
                uniq)
            if self.dilation != 1.0:
                te = te * self.dilation
                tl = tl * self.dilation
            per_sub = (te + tl)[inv_s]
            out[nz] = np.add.reduceat(per_sub, cuts)
            if pl.host_ops:
                th = self._scalar_table(
                    ("cpu_stage", pl.host_ops, max(self.sched.o, 1),
                     self.host_threads, dev.name),
                    lambda b: cpu_stage_time(pl.host_ops, b,
                                             max(self.sched.o, 1), dev,
                                             self.host_threads), uniq)[inv_s]
                if self.dilation != 1.0:
                    th = th * self.dilation
                out[nz] += wave(th, self.host_threads)
        return out


# ---------------------------------------------------------------------------
# batched slot solving (event-core fleet path)
# ---------------------------------------------------------------------------


def _reduce_queries(out, ends, counts, nz):
    """Per-query max over sub-query ends (PairService.finish epilogue)."""
    cum0 = np.concatenate([[0], np.cumsum(counts)])
    out[nz] = np.maximum.reduceat(ends, cum0[:-1][nz])


def _finish_many(jobs, fleet: bool = False) -> list[np.ndarray]:
    """Finish a batch of per-slot query streams.

    ``jobs`` is a list of ``(svc, qidx, ready, state)`` — one entry per
    slot; states are updated in place.  With ``fleet=False`` this is the
    historical sequential pass (one ``svc.finish`` per slot).  With
    ``fleet=True`` all k > 1 front pools (cpu_model thread pools, cpu_sd
    sparse pools) solve in one :func:`event_core.fleet_fifo_finish` call,
    then all dependent cpu_sd dense pools in a second — amortizing the
    per-step cost across slots (the recurrence is sequential per stream
    but embarrassingly parallel across slots).  ``k == 1`` pools keep the
    engine's Lindley dispatch and the accel admission/link/engine pipeline
    stays scalar (three coupled serialized resources, not a k-server
    pool), so every stream is bitwise-identical to its sequential
    ``svc.finish`` result."""
    if not fleet:
        return [svc.finish(qidx, ready, state=state)
                for (svc, qidx, ready, state) in jobs]
    from repro.serving import event_core

    outs: list[np.ndarray] = []
    pre: list[tuple | None] = []
    for (svc, qidx, ready, state) in jobs:
        qidx = np.asarray(qidx, np.int64)
        out = np.array(ready, dtype=np.float64, copy=True)
        outs.append(out)
        if len(qidx) == 0:
            pre.append(None)
            continue
        sub, counts = svc._sub_index(qidx)
        nz = counts > 0
        if not nz.any():
            pre.append(None)
            continue
        sub_ready = np.repeat(out, counts)
        pre.append((svc, sub, counts, nz, sub_ready, svc.inv[sub], state))

    stage1: list[tuple[int, tuple]] = []   # k>1 front pools
    for j, p in enumerate(pre):
        if p is None:
            continue
        svc, sub, counts, nz, sub_ready, inv, state = p
        if svc.plan == "cpu_model" and svc.k > 1:
            stage1.append((j, (sub_ready, svc.dur[inv], svc.k,
                               state["pool"])))
        elif svc.plan == "cpu_sd" and svc.k_sparse > 1:
            stage1.append((j, (sub_ready, svc.dur_sparse[inv],
                               svc.k_sparse, state["sparse"])))
    ends1: dict[int, np.ndarray] = {}
    for (j, _), (e, st_out) in zip(
            stage1, event_core.fleet_fifo_finish([s for _, s in stage1])):
        svc = pre[j][0]
        ends1[j] = e
        key = "pool" if svc.plan == "cpu_model" else "sparse"
        pre[j][6][key] = st_out

    stage2: list[tuple[int, tuple]] = []   # cpu_sd dense pools (chained)
    for j, p in enumerate(pre):
        if p is None:
            continue
        svc, sub, counts, nz, sub_ready, inv, state = p
        if svc.plan == "cpu_model":
            if svc.k > 1:
                e = ends1[j]
            else:
                e, state["pool"] = fifo_finish_state(
                    sub_ready, svc.dur[inv], svc.k, state["pool"])
            _reduce_queries(outs[j], e, counts, nz)
        elif svc.plan == "cpu_sd":
            if svc.k_sparse > 1:
                s_end = ends1[j]
            else:
                s_end, state["sparse"] = fifo_finish_state(
                    sub_ready, svc.dur_sparse[inv], svc.k_sparse,
                    state["sparse"])
            if svc.k > 1:
                stage2.append((j, (s_end, svc.dur_dense[inv], svc.k,
                                   state["dense"])))
            else:
                e, state["dense"] = fifo_finish_state(
                    s_end, svc.dur_dense[inv], svc.k, state["dense"])
                _reduce_queries(outs[j], e, counts, nz)
        else:
            e = svc._accel(sub_ready, svc.sub_s[sub], state)
            _reduce_queries(outs[j], e, counts, nz)
    for (j, _), (e, st_out) in zip(
            stage2, event_core.fleet_fifo_finish([s for _, s in stage2])):
        svc, sub, counts, nz, sub_ready, inv, state = pre[j]
        state["dense"] = st_out
        _reduce_queries(outs[j], e, counts, nz)
    return outs


# ---------------------------------------------------------------------------
# failure schedules
# ---------------------------------------------------------------------------


def failure_schedule(n_steps: int, n_servers: int, fail_prob: float,
                     seed: int = 0) -> list[tuple[int, int, float]]:
    """``(interval, server_type, window_frac)`` events: each server type
    loses one machine with probability ``fail_prob`` per interval, at
    ``window_frac`` of the measured query window (so failover is observed
    at query granularity).  Deterministic in ``seed`` — share one schedule
    across policies for a fair (CRN) comparison."""
    rng = np.random.default_rng(seed)
    out = []
    for t in range(n_steps):
        for h in range(n_servers):
            if rng.random() < fail_prob:
                out.append((t, h, float(rng.uniform(0.2, 0.8))))
    return out


# ---------------------------------------------------------------------------
# the day simulation, phase by phase
# ---------------------------------------------------------------------------


class _DayLog:
    """What the phases record over one day: provisioning rows, per-(workload,
    interval) measurements, carried slot states and events."""

    def __init__(self, M: int, T: int):
        self.power = np.zeros(T)                  # provisioned W incl. drain
        self.capacity = np.zeros(T, np.int64)     # machines allocated
        self.churn = np.zeros(T, np.int64)        # machines added + removed
        self.co_cap = np.zeros(T, np.int64)       # shared machines
        # per (workload, interval): latency seconds (None: not measured),
        # residual work at window end, whether the window was bridged
        self.lat_mt: list[list[np.ndarray | None]] = \
            [[None] * T for _ in range(M)]
        self.backlog_mt = np.zeros((M, T))
        self.bridged_mt: list[list[bool | None]] = \
            [[None] * T for _ in range(M)]
        # per workload: residual slot states by (group, instance)
        self.slot_states: list[dict[tuple, dict]] = [{} for _ in range(M)]
        self.n_hedged = np.zeros(M, np.int64)
        self.n_retried = np.zeros(M, np.int64)
        self.events: list[str] = []
        self.feasible = True
        self.tail_ok = True                       # last interval met SLAs

    def infeasible(self, event: str):
        self.feasible = False
        self.events.append(event)

    def provision(self, prov: StatefulProvisioner, load: np.ndarray, t: int,
                  policy: str):
        """Phase 1: provision interval ``t`` (fed the last interval's
        achieved tail) and record its power, capacity and churn."""
        step = prov.step(load, tail_ok=self.tail_ok)
        self.power[t] = step.power_w
        self.capacity[t] = step.capacity
        self.churn[t] = step.churn
        if not step.feasible:
            self.infeasible(f"t={t}: {policy} infeasible on surviving pool")
        self.co_cap[t] = len(step.coalloc)
        return step


def _query_streams(inputs: DayInputs, cfg: RuntimeConfig,
                   interval_s: float) -> tuple[SimCache, int]:
    """The day's CRN query streams and the per-(workload, interval) cap on
    the queries a window serves."""
    query_sizes = inputs.query_sizes
    if query_sizes is None:
        from repro.core.efficiency import default_query_sizes
        query_sizes = default_query_sizes()
    cache = SimCache(query_sizes, inputs.seed)
    if not cfg.event_core:
        return cache, min(cfg.queries_per_interval, _PROBE_CAP)
    cap_q = int(cfg.event_core_queries)
    # grow the CRN streams once, up front, to the day's largest interval
    # population: every window is then a bitwise prefix of the same streams
    # and no PairService ever binds stale tables
    cache.ensure(int(np.clip(float(inputs.traces.max()) * interval_s,
                             64, cap_q)))
    return cache, cap_q


def _service_pool(inputs: DayInputs, cache: SimCache):
    """``service(m, server, dilation)``: the day's :class:`PairService` of
    workload ``m`` on ``server``, built once.  A tenant of a shared machine
    gets its solo record with the duration tables dilated by the
    co-resident set's interference factor."""
    table, servers = inputs.table, inputs.servers or SERVER_TYPES

    @functools.cache
    def service(m: int, server: str, dilation: float = 1.0) -> PairService:
        name = table.workloads[m]
        return PairService(inputs.profiles[name], servers[server],
                           inputs.records[f"{name}|{server}"], cache,
                           dilation=dilation)
    return service


def _failure_victims(prov: StatefulProvisioner, table: EfficiencyTable,
                     failures: list[tuple[int, int, float]], t: int,
                     events: list[str]) -> dict[int, list[tuple]]:
    """Phase 2: take interval ``t``'s failed machines out of the pool and map
    them onto serving victims per workload, ``(group, frac)`` by the slot
    pool's machine groups: the server type ``h`` of a solo machine, or
    ``("c", server, tenants)`` of a shared one, whose loss hits every
    tenant."""
    victims_by_m: dict[int, list[tuple]] = {}
    for (ft, fh, frac) in failures:
        if ft != t:
            continue
        before = int(prov.avail[fh])
        cells = prov.fail(fh)
        if not cells:
            if int(prov.avail[fh]) < before:
                events.append(f"t={t}: spare {table.servers[fh]} failed")
            continue
        for v in cells:
            if isinstance(v, tuple):
                h, m = v
                victims_by_m.setdefault(m, []).append((int(h), frac))
                events.append(
                    f"t={t}: serving {table.servers[h]} failed "
                    f"({table.workloads[m]}) -> re-route + re-provision")
            else:
                g = ("c", v.server, v.tenants)
                for name in v.tenants:
                    victims_by_m.setdefault(
                        table.workloads.index(name), []).append((g, frac))
                events.append(
                    f"t={t}: shared {v.server} failed "
                    f"({'+'.join(v.tenants)}) -> re-route + re-provision")
    return victims_by_m


def _window(rate: float, t0: float, interval_s: float, unit_gaps: np.ndarray,
            cap_q: int, event_core: bool):
    """A workload's CRN arrivals in one interval, the window end and whether
    a window short of the interval end is bridged by stationarity (the
    event core measures it to the boundary instead)."""
    n = int(np.clip(rate * interval_s, 64, cap_q))
    arrivals = t0 + np.cumsum(unit_gaps[:n] * (1.0 / rate))
    w_end = float(arrivals[-1])
    bridged = bool(n == cap_q and w_end < t0 + interval_s)
    if event_core:
        w_end = max(w_end, t0 + interval_s)
    return arrivals, w_end, bridged


@dataclasses.dataclass
class _SlotPool:
    """One workload's router slots in one interval with, per slot, its
    machine group, its service, its absolute pool state (updated in place
    by the passes) and the key it carries out under (None: a draining or
    failed machine)."""

    slots: list[ServerSlot] = dataclasses.field(default_factory=list)
    groups: list = dataclasses.field(default_factory=list)
    pair_of: list[PairService] = dataclasses.field(default_factory=list)
    states: list[dict] = dataclasses.field(default_factory=list)
    keys: list[tuple | None] = dataclasses.field(default_factory=list)

    def add_group(self, group, serving: list, keep: int, draining: list,
                  t0: float, transitions: TransitionConfig,
                  prev_states: dict):
        """One machine group's slots: ``serving`` (the ``keep`` kept machines
        first, then the added ones), then ``draining``.  Each entry is
        ``(svc, server, rate, machine)``, or None for a machine that does
        not serve this workload (it keeps its index).  Kept machines resume
        their carried ``(group, i)`` state, added ones start idle once their
        model is loaded, draining ones inherit their backlog and take
        queries until ``drain_s``."""
        for i, slot in enumerate(serving):
            fresh = i >= keep
            self._add(group, slot,
                      None if fresh else prev_states.get((group, i)), t0,
                      t0 + transitions.model_load_s if fresh else t0,
                      float("inf"), (group, i))
        for j, slot in enumerate(draining):
            self._add(group, slot, prev_states.get((group, keep + j)), t0, t0,
                      t0 + transitions.drain_s, None)

    def _add(self, group, slot, residual: dict | None, t0: float,
             ready: float, retire: float, key):
        if slot is None:
            return
        svc, server, rate, machine = slot
        self.slots.append(ServerSlot(server, rate, ready_at=ready,
                                     retire_at=retire, machine=machine))
        self.groups.append(group)
        self.pair_of.append(svc)
        self.states.append(_state_abs(
            residual if residual is not None else svc.fresh_state(), t0))
        self.keys.append(key)


def _by_group(machines, name: str) -> dict[tuple, list]:
    """The shared machines serving ``name``, by ("c", server, tenants)."""
    out: dict[tuple, list] = {}
    for c in machines:
        if name in c.tenants:
            out.setdefault(("c", c.server, c.tenants), []).append(c)
    return out


def _slot_pool(step, m: int, name: str, t0: float,
               transitions: TransitionConfig, prev_states: dict, service,
               server_names) -> _SlotPool:
    """Phase 3: workload ``m``'s slot pool in one interval.  Solo machines
    group by server type ``h``, in ``h`` order; shared machines follow, one
    slot per tenant pool per machine, weighted by the tenant's rate and
    grouped by ("c", server, tenants) in sorted order, their identity that
    group plus the instance, so a hardware failure hits every tenant."""
    pool = _SlotPool()
    for h, server in enumerate(server_names):
        cnt, rem = int(step.alloc[h, m]), int(step.removed[h, m])
        if cnt + rem == 0:
            continue
        svc = service(m, server)
        solo = (svc, server, svc.qps, None)
        pool.add_group(h, [solo] * cnt, cnt - int(step.added[h, m]),
                       [solo] * rem, t0, transitions, prev_states)

    def shared(c, g: tuple, i: int):
        rate_c = c.rate_of(name)
        if rate_c <= 0.0:
            return None
        return (service(m, c.server, c.dilation_of(name)), c.server, rate_c,
                g + (i,))

    cur_g, rem_g, add_g = (_by_group(x, name) for x in (
        step.coalloc, step.co_removed, step.co_added))
    for g in sorted(set(cur_g) | set(rem_g)):
        pend = list(add_g.get(g, []))
        kept, fresh = [], []
        for c in cur_g.get(g, []):
            if c in pend:
                pend.remove(c)
                fresh.append(c)
            else:
                kept.append(c)
        cur = kept + fresh
        pool.add_group(g, [shared(c, g, i) for i, c in enumerate(cur)],
                       len(kept), [shared(c, g, len(cur) + j)
                                   for j, c in enumerate(rem_g.get(g, []))],
                       t0, transitions, prev_states)
    return pool


def _retire_victims(pool: _SlotPool, victims: list[tuple],
                    arrivals: np.ndarray) -> list[tuple[int, float]]:
    """Phase 4a: each victim, the first slot of its machine group (a solo
    server type ``h`` or a shared ``("c", server, tenants)``) taking queries
    at its failure time, ``frac`` of the way through the window's arrivals,
    stops there and carries nothing out.  Returns ``(slot, t_f)`` per
    retired slot."""
    fail_times: list[tuple[int, float]] = []
    for (g, frac) in victims:
        t_f = float(arrivals[0] + frac * (arrivals[-1] - arrivals[0]))
        vi = next((i for i, s in enumerate(pool.slots)
                   if pool.groups[i] == g and s.accepts(t_f)), None)
        if vi is None:
            continue
        pool.slots[vi].retire_at = t_f
        pool.keys[vi] = None
        fail_times.append((vi, t_f))
    return fail_times


def _serve_window(router: QueryRouter, pool: _SlotPool, arrivals: np.ndarray,
                  fail_times: list[tuple[int, float]], fleet: bool,
                  log: _DayLog, tag: str):
    """Phase 4b: route the window and serve it.  Failed slots go first:
    queries they finish before the failure complete, the rest re-dispatch
    to healthy slots at the detection time; then every slot serves its
    stream in one pass.  Returns ``(assigned, latency, streams, n_retried)``
    (``streams``: each slot's ``(queries, ready)``), or None if no slot was
    ready."""
    try:
        assigned = router.assign_stream(arrivals)
    except RuntimeError:
        log.infeasible(f"{tag} had no ready servers in the window")
        return None
    ready = arrivals.copy()
    latency = np.zeros(len(arrivals))
    done = np.zeros(len(arrivals), bool)
    n_retried = 0
    for (vi, t_f) in fail_times:
        qv = np.flatnonzero(assigned == vi)
        if len(qv) == 0:
            router.mark_failed(pool.slots[vi])
            continue
        # an earlier victim's retries may have landed here: FIFO order is
        # by ready time, not stream index
        qv = qv[np.argsort(ready[qv], kind="stable")]
        f = pool.pair_of[vi].finish(qv, ready[qv], state=pool.states[vi])
        ok = f <= t_f
        latency[qv[ok]] = f[ok] - arrivals[qv[ok]]
        done[qv[ok]] = True
        router.mark_failed(pool.slots[vi])
        lost = qv[~ok]
        if len(lost):
            ready[lost] = t_f
            try:
                assigned[lost] = router.assign_stream(ready[lost])
                n_retried += len(lost)
            except RuntimeError:
                latency[lost] = np.inf
                done[lost] = True
                log.infeasible(f"{tag} lost queries — no healthy servers "
                               "left to retry on")
    streams: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    jobs: list[tuple] = []
    for si, svc in enumerate(pool.pair_of):
        qs = np.flatnonzero((assigned == si) & ~done)
        if len(qs):
            qs = qs[np.argsort(ready[qs], kind="stable")]
            jobs.append((svc, qs, ready[qs], pool.states[si]))
            streams[si] = (qs, ready[qs])
    for (_, qs, _, _), f in zip(jobs, _finish_many(jobs, fleet=fleet)):
        latency[qs] = f - arrivals[qs]
    return assigned, latency, streams, n_retried


_NO_STREAM = (np.zeros(0, np.int64), np.zeros(0))


def _hedge_first_pass(pool: _SlotPool, carry_in: list[dict],
                      straggler: np.ndarray, alt: np.ndarray,
                      arrivals: np.ndarray, latency: np.ndarray,
                      streams: dict, thr: float) -> int:
    """Phase 5, first-pass model: a duplicate issued at arrival + ``thr``
    joins the alternate slot's live queue (its carried backlog plus its
    in-window stream); first completion wins.  The primaries' first-pass
    latencies stand, but the slot's carried state takes in the hedge work.
    Updates ``latency`` and ``pool.states``; returns the queries sped up."""
    n_hedged = 0
    ok = alt >= 0
    for a in np.unique(alt[ok]):
        sel = straggler[ok & (alt == a)]
        prim_q, prim_r = streams.get(a, _NO_STREAM)
        mq = np.concatenate([prim_q, sel])
        mr = np.concatenate([prim_r, arrivals[sel] + thr])
        order = np.argsort(mr, kind="stable")
        st = _state_copy(carry_in[a])
        f_all = pool.pair_of[a].finish(mq[order], mr[order], state=st)
        pos = np.empty(len(mq), np.int64)
        pos[order] = np.arange(len(mq))
        hedged = f_all[pos[len(prim_q):]] - arrivals[sel]
        pool.states[a] = st
        better = hedged < latency[sel]
        latency[sel[better]] = hedged[better]
        n_hedged += int(better.sum())
    return n_hedged


def _hedge_event_ordered(pool: _SlotPool, carry_in: list[dict],
                         straggler: np.ndarray, alt: np.ndarray,
                         arrivals: np.ndarray, latency: np.ndarray,
                         streams: dict, thr: float) -> int:
    """Phase 5, event core: each target slot re-serves its primaries with
    the duplicates merged in at their issue times, so the primaries'
    latencies now reflect the duplicates' admission; each straggler keeps
    first-completion-wins.  Same contract as :func:`_hedge_first_pass`."""
    ok = alt >= 0
    hjobs, hmeta = [], []
    for a in np.unique(alt[ok]):
        sel = straggler[ok & (alt == a)]
        prim_q, prim_r = streams.get(a, _NO_STREAM)
        times, order = merge_event_streams(prim_r, arrivals[sel] + thr)
        st = _state_copy(carry_in[a])
        hjobs.append((pool.pair_of[a], np.concatenate([prim_q, sel])[order],
                      times, st))
        hmeta.append((a, sel, prim_q, order, st))
    # apply all primary re-serves first, then the duplicate minima, so a
    # straggler that is also a perturbed primary competes against its
    # updated first-pass finish
    dup_lat = []
    for (a, sel, prim_q, order, st), f_all in zip(
            hmeta, _finish_many(hjobs, fleet=True)):
        pos = np.empty(len(order), np.int64)
        pos[order] = np.arange(len(order))
        latency[prim_q] = f_all[pos[:len(prim_q)]] - arrivals[prim_q]
        dup_lat.append(f_all[pos[len(prim_q):]] - arrivals[sel])
        pool.states[a] = st
    n_hedged = 0
    for (_, sel, _, _, _), hedged in zip(hmeta, dup_lat):
        better = hedged < latency[sel]
        latency[sel[better]] = hedged[better]
        n_hedged += int(better.sum())
    return n_hedged


def _carry_out(states: list[dict], keys: list[tuple | None],
               w_end: float) -> tuple[dict[tuple, dict], float]:
    """Phase 6: machines that served through the window keep their residual
    backlog under a compacted instance index per group (failed and
    draining slots carry nothing).  Returns the carried states and the
    interval's backlog in seconds of residual work."""
    new_states: dict[tuple, dict] = {}
    counters: dict = {}
    bl = 0.0
    for st, key in zip(states, keys):
        if key is None:
            continue
        idx = counters.get(key[0], 0)
        counters[key[0]] = idx + 1
        bl += _drain_horizon(st, w_end)
        new_states[(key[0], idx)] = _state_residual(st, w_end)
    return new_states, bl


def _tail_met(lat_t: list[np.ndarray | None], slas: list[float],
              quantile: float) -> bool:
    """Phase 7: whether every workload measured this interval met its SLA
    quantile (a lost query, of infinite latency, fails it)."""
    for lat, sla in zip(lat_t, slas):
        if lat is None:
            continue
        if not np.isfinite(lat).all() or \
                float(np.quantile(lat, quantile)) * 1e3 > sla:
            return False
    return True


def _percentiles(lat_ms: np.ndarray) -> tuple[float, float, float]:
    p50, p95, p99 = np.percentile(lat_ms, (50, 95, 99))
    return float(p50), float(p95), float(p99)


def _workload_day(lats: list[np.ndarray | None], sla: float,
                  quantile: float) -> tuple[dict, dict]:
    """Phase 8 for one workload: its day figures and its per-interval
    (Fig. 8b-style) series."""
    measured = [lat for lat in lats if lat is not None]
    lat_ms = np.concatenate(measured) * 1e3 if measured else \
        np.array([np.inf])
    p50, p95, p99 = _percentiles(lat_ms)
    q = float(np.quantile(lat_ms, quantile))
    s: dict[str, list] = {k: [] for k in (
        "p50_ms", "p95_ms", "p99_ms", "sla_attainment", "meets_sla",
        "n_queries")}
    met_t = 0
    for lat in lats:
        if lat is None:
            for k in s:
                s[k].append(None)
            continue
        ms = lat * 1e3
        for k, v in zip(("p50_ms", "p95_ms", "p99_ms"), _percentiles(ms)):
            s[k].append(v)
        s["sla_attainment"].append(float(np.mean(ms <= sla)))
        im = bool(float(np.quantile(ms, quantile)) <= sla)
        s["meets_sla"].append(im)
        s["n_queries"].append(int(len(ms)))
        met_t += im
    return {
        "sla_ms": sla, "p50_ms": p50, "p95_ms": p95, "p99_ms": p99,
        "sla_attainment": float(np.mean(lat_ms <= sla)),
        "meets_sla": bool(q <= sla),
        "interval_sla_met_frac":
            float(met_t / len(measured)) if measured else 0.0,
        "n_queries": int(len(lat_ms)),
    }, s


def _day_result(log: _DayLog, prov: StatefulProvisioner, policy: str,
                names, slas: list[float], interval_s: float,
                cfg: RuntimeConfig, colocated: bool) -> DayResult:
    """Phase 8: the day-level aggregates, the ``series`` block and the
    :class:`DayResult`."""
    workloads, series = {}, {}
    for m, name in enumerate(names):
        figures, s = _workload_day(log.lat_mt[m], slas[m], cfg.sla_quantile)
        s["backlog_s"] = [float(b) for b in log.backlog_mt[m]]
        # True = stationarity-bridged past the query cap; False = fully
        # simulated; None = interval not measured
        s["bridged"] = log.bridged_mt[m]
        series[name] = s
        workloads[name] = {**figures, "n_hedged": int(log.n_hedged[m]),
                           "n_retried": int(log.n_retried[m])}
    return DayResult(
        policy=policy, power=log.power, capacity=log.capacity,
        churn=log.churn, feasible=log.feasible,
        peak_power_w=float(log.power.max()),
        avg_power_w=float(log.power.mean()),
        peak_capacity=int(log.capacity.max()),
        avg_capacity=float(log.capacity.mean()),
        resolves=prov.n_resolves, holds=prov.n_holds,
        tail_resolves=prov.n_tail_resolves,
        total_churn=int(log.churn.sum()), per_workload=workloads,
        series={"interval_s": interval_s, "per_workload": series},
        all_meet_sla=all(w["meets_sla"] for w in workloads.values()),
        events=log.events,
        latencies=log.lat_mt if cfg.collect_latencies else None,
        co_capacity=log.co_cap if colocated else None)


def simulate_cluster_day(inputs: DayInputs, policy: str = "hercules",
                         config: RuntimeConfig | None = None) -> DayResult:
    """Serve a full diurnal day at query granularity, continuous in time.

    ``inputs`` is a :class:`DayInputs` (``table``/``records`` from
    ``efficiency.build_table``, ``profiles`` mapping workload name ->
    :class:`ModelProfile`); ``policy`` and ``config`` select how the day is
    served.  Returns a :class:`DayResult`: the provisioning series (power
    incl. transition drain, capacity, resolves/holds/churn), *achieved*
    per-workload latency percentiles and SLA attainment — the numbers
    ``provision_day`` only asserts via the QPS column — plus a per-interval
    ``series`` block (the Fig. 8b-style SLA-over-the-day record) and the
    carried-backlog trajectory.  Each interval runs the phases above in
    their order, the tail check last.
    """
    cfg = config or RuntimeConfig()
    table, traces = inputs.table, inputs.traces
    transitions = inputs.transitions or TransitionConfig()
    M, T = traces.shape
    cache, cap_q = _query_streams(inputs, cfg, transitions.interval_s)
    service = _service_pool(inputs, cache)
    prov = StatefulProvisioner(table, policy, inputs.overprovision,
                               transitions, seed=inputs.seed,
                               colocation=inputs.colocation)
    routers = [QueryRouter([], hedge_quantile=cfg.hedge_quantile,
                           hedge_factor=cfg.hedge_factor, seed=inputs.seed + m)
               for m in range(M)]
    slas = [inputs.profiles[name].sla_ms for name in table.workloads]
    log = _DayLog(M, T)

    for t in range(T):
        step = log.provision(prov, traces[:, t], t, policy)
        t0 = t * transitions.interval_s
        victims = _failure_victims(prov, table, inputs.failures or [], t,
                                   log.events)
        for m, name in enumerate(table.workloads):
            rate = float(traces[m, t])
            if rate <= 0.0:
                log.slot_states[m] = {}  # a whole idle interval drains it
                continue
            if step.alloc[:, m].sum() == 0 and not any(
                    name in c.tenants and c.rate_of(name) > 0.0
                    for c in step.coalloc):
                log.infeasible(f"t={t}: {name} unallocated")
                log.slot_states[m] = {}
                continue
            arrivals, w_end, log.bridged_mt[m][t] = _window(
                rate, t0, transitions.interval_s, cache.unit_gaps, cap_q,
                cfg.event_core)
            pool = _slot_pool(step, m, name, t0, transitions,
                              log.slot_states[m], service, table.servers)
            router = routers[m]
            router.refresh(pool.slots)
            thr = router.hedge_threshold()
            hedging = bool(np.isfinite(thr)) and len(pool.slots) > 1
            # the slots' states entering the window: a hedge target's
            # merged pass re-serves its stream from there
            carry_in = [_state_copy(st) for st in pool.states] \
                if hedging else None
            fail_times = _retire_victims(pool, victims.get(m, []), arrivals)
            served = _serve_window(router, pool, arrivals, fail_times,
                                   cfg.event_core, log, f"t={t}: {name}")
            if served is None:
                log.slot_states[m] = {}
                continue
            assigned, latency, streams, n_retried = served
            log.n_retried[m] += n_retried
            if hedging:
                straggler, _, alt = router.hedge_events(
                    assigned, arrivals, latency, thr)
                if len(straggler):
                    hedge = _hedge_event_ordered if cfg.event_core \
                        else _hedge_first_pass
                    log.n_hedged[m] += hedge(pool, carry_in, straggler, alt,
                                             arrivals, latency, streams, thr)
            router.observe_many(latency[np.isfinite(latency)])
            log.lat_mt[m][t] = latency
            log.slot_states[m], log.backlog_mt[m, t] = _carry_out(
                pool.states, pool.keys, w_end)
        log.tail_ok = _tail_met([log.lat_mt[m][t] for m in range(M)], slas,
                                cfg.sla_quantile)

    return _day_result(log, prov, policy, table.workloads, slas,
                       transitions.interval_s, cfg,
                       inputs.colocation is not None)
