"""Stateful provisioning + the query-granular cluster serving runtime:
transition-delay accounting, hysteresis, elastic re-provisioning after
failures, router stream assignment, PairService <-> fast-engine
equivalence, and the day loop's phases on hand-built intervals."""
import pathlib
import tempfile

import numpy as np
import pytest

from repro.configs.paper_models import paper_profile
from repro.core import profile_cache
from repro.core.cluster import (
    EfficiencyTable,
    StatefulProvisioner,
    StatefulStep,
    TransitionConfig,
)
from repro.core.colocation import CoMachine
from repro.core.devices import SERVER_TYPES
from repro.core.efficiency import build_table, default_query_sizes
from repro.core.partition import enumerate_placements
from repro.serving.cluster_runtime import (
    DayInputs,
    PairService,
    RuntimeConfig,
    _carry_out,
    _retire_victims,
    _slot_pool,
    _state_abs,
    _state_residual,
    _tail_met,
    simulate_cluster_day,
)
from repro.serving.diurnal import diurnal_trace, load_increment_rate
from repro.serving.router import QueryRouter, ServerSlot
from repro.serving.simulator import SchedConfig, SimCache, _run_plan


def _table1(qps=100.0, avail=20):
    return EfficiencyTable(("s0",), ("w0",), np.array([[qps]]),
                           np.array([[200.0]]), np.array([avail]))


def _day(table, records, profiles, traces, *, policy="hercules",
         config=None, **inputs_kw):
    """Serve one day through the typed API: bundle the day's data into
    :class:`DayInputs`, keep policy/config as call-site arguments."""
    return simulate_cluster_day(
        DayInputs(table=table, records=records, profiles=profiles,
                  traces=traces, **inputs_kw),
        policy=policy, config=config)


class TestStatefulProvisioner:
    def test_hysteresis_suppresses_flapping(self):
        prov = StatefulProvisioner(_table1(), overprovision=0.05,
                                   transitions=TransitionConfig(hysteresis=0.10))
        s0 = prov.step(np.array([1000.0]))
        assert s0.resolved and s0.feasible and s0.capacity == 11
        # single-interval wiggles inside the band: held, zero churn
        for load in (1020.0, 980.0, 1005.0):
            s = prov.step(np.array([load]))
            assert not s.resolved and s.churn == 0
            assert (s.alloc == s0.alloc).all()
        # out-of-band growth: re-solve, servers added
        s = prov.step(np.array([1500.0]))
        assert s.resolved and s.added.sum() > 0 and s.removed.sum() == 0
        assert prov.n_holds == 3 and prov.n_resolves == 2

    def test_band_hold_requires_coverage(self):
        # inside the band but no longer covered (capacity lost) -> re-solve
        prov = StatefulProvisioner(_table1(avail=20), overprovision=0.05)
        prov.step(np.array([1000.0]))
        prov.alloc[0, 0] -= 2  # exogenous capacity loss
        s = prov.step(np.array([1000.0]))
        assert s.resolved and s.capacity == 11

    def test_transition_power_accounting(self):
        cfg = TransitionConfig(interval_s=900.0, model_load_s=120.0,
                               drain_s=150.0, hysteresis=0.0)
        t = _table1()
        prov = StatefulProvisioner(t, overprovision=0.0, transitions=cfg)
        s1 = prov.step(np.array([1000.0]))          # warm start: no transient
        assert s1.added.sum() == 0 and s1.power_w == 10 * 200.0
        s2 = prov.step(np.array([1500.0]))          # growth: adds, no drain
        assert s2.added.sum() == 5 and s2.removed.sum() == 0
        assert s2.power_w == 15 * 200.0
        s3 = prov.step(np.array([500.0]))           # shrink: drain power tail
        assert s3.added.sum() == 0 and s3.removed.sum() == 10
        assert s3.power_w == pytest.approx(
            5 * 200.0 + 10 * 200.0 * cfg.drain_s / cfg.interval_s)

    def test_fail_all_serving_takes_victim_and_forces_resolve(self):
        prov = StatefulProvisioner(_table1(avail=3), overprovision=0.0)
        s = prov.step(np.array([280.0]))
        assert s.capacity == 3  # the whole pool serves
        victims = prov.fail(0)
        assert victims == [(0, 0)]
        assert prov.avail[0] == 2 and prov.alloc[0, 0] == 2
        s2 = prov.step(np.array([280.0]))           # needs 3, only 2 left
        assert s2.resolved and not s2.feasible
        s3 = prov.step(np.array([150.0]))           # shrunken pool suffices
        assert s3.feasible

    def test_fail_spare_leaves_alloc_alone(self):
        prov = StatefulProvisioner(_table1(avail=20), overprovision=0.0,
                                   seed=0)
        prov.step(np.array([100.0]))  # 1 of 20 serving
        # 19 spares: overwhelmingly likely the victim is idle
        hits = sum(bool(prov.fail(0)) for _ in range(3))
        assert prov.avail[0] == 17
        assert prov.alloc[0, 0] + hits == 1


class TestRouterStream:
    def test_weight_proportional_and_deterministic(self):
        slots = [ServerSlot("a", 300.0), ServerSlot("b", 100.0)]
        r1 = QueryRouter(list(slots), seed=3)
        r2 = QueryRouter(list(slots), seed=3)
        arr = np.linspace(0.0, 1.0, 10_000)
        a1, a2 = r1.assign_stream(arr), r2.assign_stream(arr)
        assert (a1 == a2).all()
        frac = (a1 == 0).mean()
        assert abs(frac - 0.75) < 0.01

    def test_ready_and_retire_windows(self):
        slots = [ServerSlot("old", 100.0, retire_at=0.5),
                 ServerSlot("new", 100.0, ready_at=0.5)]
        router = QueryRouter(slots, seed=0)
        arr = np.linspace(0.0, 1.0, 1000, endpoint=False)
        a = router.assign_stream(arr)
        assert (a[arr < 0.5] == 0).all()
        assert (a[arr >= 0.5] == 1).all()

    def test_no_acceptor_raises(self):
        router = QueryRouter([ServerSlot("a", 100.0, ready_at=5.0)], seed=0)
        with pytest.raises(RuntimeError):
            router.assign_stream(np.array([0.0, 1.0]))


SIZES = default_query_sizes(300, seed=0)


class TestPairServiceMatchesEngine:
    """A slot receiving the whole CRN stream must reproduce the PR-2 fast
    engine bit-for-bit — the runtime's service model *is* the simulator."""

    def _check(self, workload, server, plan, sched):
        prof = paper_profile(workload)
        dev = SERVER_TYPES[server]
        cache = SimCache(SIZES, seed=0)
        rec = {"qps": 1000.0, "plan": plan, "m": sched.m, "d": sched.batch,
               "o": sched.o, "sd_sparse": sched.sd_sparse}
        svc = PairService(prof, dev, rec, cache)
        n = 400
        arrivals = np.cumsum(cache.unit_gaps[:n] * (1.0 / 900.0))
        got = svc.finish(np.arange(n), arrivals)
        pl = next(p for p in enumerate_placements(prof, dev) if p.plan == plan)
        want, _ = _run_plan(pl, dev, sched, arrivals, cache.sized[:n],
                            "fast", cache.tables, n)
        np.testing.assert_allclose(got, want, rtol=1e-12)

    def test_cpu_model(self):
        self._check("dlrm-rmc1", "T2", "cpu_model",
                    SchedConfig(batch=64, m=4, o=2))

    def test_cpu_sd(self):
        self._check("dlrm-rmc1", "T2", "cpu_sd",
                    SchedConfig(batch=64, m=8, o=2, sd_sparse=6))

    def test_accel_hot(self):
        self._check("dlrm-rmc3", "T7", "accel_hot",
                    SchedConfig(batch=256, m=2, o=2))


class TestContinuousTime:
    """Backlog carry-over: a stream split at any window boundary and
    re-served from the carried state must reproduce the unsplit run (the
    conservation property behind continuous-time windows)."""

    def _svc(self, workload, server, plan, sched, cache):
        rec = {"qps": 1000.0, "plan": plan, "m": sched.m, "d": sched.batch,
               "o": sched.o, "sd_sparse": sched.sd_sparse}
        return PairService(paper_profile(workload), SERVER_TYPES[server],
                           rec, cache)

    @pytest.mark.parametrize("plan,sched", [
        ("cpu_model", SchedConfig(batch=64, m=4, o=2)),
        ("cpu_sd", SchedConfig(batch=64, m=8, o=2, sd_sparse=6)),
    ])
    def test_window_split_equals_whole(self, plan, sched):
        cache = SimCache(SIZES, seed=0)
        svc = self._svc("dlrm-rmc1", "T2", plan, sched, cache)
        n = 250
        # overloaded rate, so backlog genuinely spans the boundary
        arr = np.cumsum(cache.unit_gaps[:2 * n] * (1.0 / 4000.0))
        whole = svc.finish(np.arange(2 * n), arr)
        st = _state_abs(svc.fresh_state(), 0.0)
        a1 = svc.finish(np.arange(n), arr[:n], state=st)
        w_end = float(arr[n - 1])
        st2 = _state_abs(_state_residual(st, w_end), w_end)
        a2 = svc.finish(np.arange(n, 2 * n), arr[n:], state=st2)
        np.testing.assert_allclose(np.concatenate([a1, a2]), whole,
                                   rtol=1e-12)
        # and the carried backlog was real: window 2 started loaded
        assert max(float(v.max()) for v in st.values()) > w_end

    def test_backlog_persists_across_intervals(self, small_cluster):
        """A fleet pinned just past its feasibility frontier (the re-solve
        is infeasible, the pool serves best-effort at ~103% utilization)
        accumulates backlog interval over interval: the carried state is
        never reset to an idle pool at an interval boundary."""
        table, records, profiles, servers = small_cluster
        t1 = EfficiencyTable(("T2",), ("dlrm-rmc1",),
                             table.qps[:1, :1], table.power[:1, :1],
                             np.array([4]))
        cap = 4 * float(t1.qps[0, 0])
        traces = np.concatenate([[0.90], np.full(5, 1.03)])[None, :] * cap
        out = _day(t1, records, profiles, traces,
                   servers=servers, overprovision=0.05, seed=0)
        s = out.series["per_workload"]["dlrm-rmc1"]
        # monotone growth through the overloaded stretch
        backlog = s["backlog_s"]
        assert all(a < b for a, b in zip(backlog[1:], backlog[2:]))
        # the carried backlog compounds into the tail
        assert s["p95_ms"][-1] > 5.0 * s["p95_ms"][0]


class TestLiveQueueHedging:
    def test_hedge_rides_the_live_queue(self):
        """A hedge admitted into a busy alternate completes strictly later
        than its unloaded service time: completion >= issue + solo_time,
        with equality only on an idle pool."""
        cache = SimCache(SIZES, seed=0)
        rec = {"qps": 1000.0, "plan": "cpu_model", "m": 4, "d": 64,
               "o": 2, "sd_sparse": 0}
        svc = PairService(paper_profile("dlrm-rmc1"), SERVER_TYPES["T2"],
                          rec, cache)
        n = 200
        prim = np.arange(n)
        arr = np.cumsum(cache.unit_gaps[:n] * (1.0 / 4000.0))  # overloaded
        hq = np.array([n + 5])
        t_issue = np.array([float(arr[n // 2])])  # lands mid-backlog
        merged_q = np.concatenate([prim, hq])
        merged_r = np.concatenate([arr, t_issue])
        order = np.argsort(merged_r, kind="stable")
        st = _state_abs(svc.fresh_state(), 0.0)
        f_all = svc.finish(merged_q[order], merged_r[order], state=st)
        pos = np.empty(len(merged_q), np.int64)
        pos[order] = np.arange(len(merged_q))
        f_hedge = float(f_all[pos[n]])
        solo = float(svc.solo_time(hq)[0])
        live_wait = f_hedge - float(t_issue[0])
        assert live_wait >= solo - 1e-12
        assert live_wait > 2.0 * solo  # the queue was busy: much slower
        # idle pool: the live-queue model degenerates to the unloaded time
        st_idle = _state_abs(svc.fresh_state(), 0.0)
        f_idle = svc.finish(hq, t_issue, state=st_idle)
        assert float(f_idle[0]) - float(t_issue[0]) == pytest.approx(
            solo, rel=1e-9)

    def test_hedge_assign_targets(self):
        slots = [ServerSlot("a", 100.0), ServerSlot("b", 300.0),
                 ServerSlot("c", 200.0, ready_at=10.0)]
        router = QueryRouter(slots, seed=0)
        prim = np.array([1, 0, 1])
        t_issue = np.array([0.0, 0.0, 20.0])
        alt = router.hedge_assign(prim, t_issue)
        # never the primary; fastest accepting slot at issue time
        assert alt.tolist() == [0, 1, 2]
        # failed + not-yet-ready slots can't take a duplicate
        router.mark_failed(slots[0])
        assert router.hedge_assign(np.array([1]),
                                   np.array([0.0])).tolist() == [-1]


class TestTailFeedback:
    def test_violation_vetoes_hold_and_boosts(self):
        cfg = TransitionConfig(hysteresis=0.50, feedback_boost=0.30)
        prov = StatefulProvisioner(_table1(), overprovision=0.0,
                                   transitions=cfg)
        s0 = prov.step(np.array([1000.0]))
        assert s0.capacity == 10
        s1 = prov.step(np.array([1000.0]), tail_ok=True)
        assert not s1.resolved            # in-band: held
        s2 = prov.step(np.array([1000.0]), tail_ok=False)
        assert s2.resolved                # violation vetoes the hold
        assert s2.capacity == 13          # 1000 * 1.3 -> 13 servers
        assert prov.n_tail_resolves == 1

    def test_boost_infeasible_falls_back_to_offered_load(self):
        """When the pool cannot fund the feedback headroom but can still
        cover the offered load, the re-solve serves the offered load
        rather than freezing on the stale (undersized) allocation."""
        prov = StatefulProvisioner(_table1(avail=10), overprovision=0.0)
        prov.step(np.array([500.0]))      # 5 of 10 serving
        s = prov.step(np.array([950.0]), tail_ok=False)
        # boosted target 1045 needs 11 > 10 servers; offered load fits
        assert s.feasible and s.capacity == 10
        assert prov.n_tail_resolves == 1

    def test_feedback_recovers_underprovisioned_day(self, small_cluster):
        """A fleet sized to offered load alone sits at ~95% utilization and
        diverges; achieved-tail feedback adds the machine the offered load
        cannot justify and the backlog drains."""
        table, records, profiles, servers = small_cluster
        t1 = EfficiencyTable(("T2",), ("dlrm-rmc1",),
                             table.qps[:1, :1], table.power[:1, :1],
                             np.array([6]))
        cap = 6 * float(t1.qps[0, 0])
        traces = np.full((1, 8), 0.60 * cap)
        fb = _day(t1, records, profiles, traces,
                  servers=servers, overprovision=0.05, seed=1)
        assert fb.tail_resolves > 0
        assert fb.capacity[-1] > fb.capacity[0]             # grew the fleet
        s = fb.series["per_workload"]["dlrm-rmc1"]
        assert s["p95_ms"][-1] < max(s["p95_ms"])           # drained
        assert s["meets_sla"][-1]


@pytest.fixture(scope="module")
def small_cluster():
    """Profiled 2-workload x 3-server setup (hermetic profile cache) —
    the same topology the scenario zoo registers its smoke specs on."""
    from repro.serving.scenarios import (
        SMOKE_AVAILABILITY,
        SMOKE_SERVERS,
        SMOKE_WORKLOADS,
    )
    mp = pytest.MonkeyPatch()
    tmp = pathlib.Path(tempfile.mkdtemp())
    mp.setattr(profile_cache, "PROFILE_DIR", tmp)
    profiles = {n: paper_profile(n) for n in SMOKE_WORKLOADS}
    servers = {s: SERVER_TYPES[s] for s in SMOKE_SERVERS}
    table, records = build_table(profiles, servers,
                                 dict(SMOKE_AVAILABILITY))
    yield table, records, profiles, servers
    mp.undo()


def _traces(table, frac, n_steps):
    cap = table.fleet_capacity()
    return np.stack([diurnal_trace(frac * cap[m], seed=m, n_steps=n_steps)
                     for m in range(len(table.workloads))])


class TestClusterRuntime:
    def test_sla_attained_at_benchmark_fraction(self, small_cluster):
        """At the benchmark's comparison load fraction, the runtime's
        achieved latency meets every workload's SLA for both hercules and
        greedy, and hercules provisions no more peak power."""
        table, records, profiles, servers = small_cluster
        traces = _traces(table, 0.09, 24)
        R = max(load_increment_rate(t) for t in traces)
        out = {}
        for pol in ("greedy", "hercules"):
            out[pol] = _day(
                table, records, profiles, traces, policy=pol,
                servers=servers, overprovision=R)
            assert out[pol].feasible, pol
            assert out[pol].all_meet_sla, (pol, out[pol].per_workload)
            for w in out[pol].per_workload.values():
                assert w["sla_attainment"] >= 0.95
        assert out["hercules"].peak_power_w <= \
            out["greedy"].peak_power_w + 1e-6

    def test_flat_load_holds_allocation(self, small_cluster):
        """Hysteresis: jitter inside the band never re-provisions."""
        table, records, profiles, servers = small_cluster
        M = len(table.workloads)
        cap = table.fleet_capacity()
        rng = np.random.default_rng(0)
        flat = np.stack([
            0.08 * cap[m] * (1.0 + 0.02 * rng.standard_normal(12))
            for m in range(M)
        ])
        out = _day(table, records, profiles, flat,
                   servers=servers, overprovision=0.10)
        assert out.resolves == 1 and out.holds == 11
        assert out.total_churn == 0 and out.all_meet_sla

    def test_failure_reroutes_and_reprovisions(self, small_cluster):
        """A serving machine dies mid-window: its unfinished queries retry
        on healthy slots, the provisioner re-solves on the shrunken pool,
        and the day stays feasible with SLAs met."""
        table, records, profiles, servers = small_cluster
        # single-type fleet sized so nearly every machine serves: the
        # victim of a type-wide failure is a serving box
        t1 = EfficiencyTable(("T2",), ("dlrm-rmc1",),
                             table.qps[:1, :1], table.power[:1, :1],
                             np.array([6]))
        cap = 6 * float(t1.qps[0, 0])
        # flat load needing 5 of the 6 machines: the failure victim is a
        # serving box (deterministic for this seed), and the surviving
        # spare lets the re-solve keep the day feasible
        traces = np.full((1, 8), 0.65 * cap)
        out = _day(
            t1, records, profiles, traces,
            servers=servers, overprovision=0.05,
            failures=[(2, 0, 0.5)], seed=1)
        assert out.feasible
        assert any("serving T2 failed" in e for e in out.events)
        w = out.per_workload["dlrm-rmc1"]
        assert w["n_retried"] > 0         # in-flight queries re-dispatched
        assert out.resolves >= 2          # elastic re-provision after loss
        # the spare absorbs the loss: steady capacity is restored
        assert out.capacity[-1] == out.capacity[0]
        # ~80% per-slot utilization plus a machine loss dents the tail but
        # the fleet keeps serving; the carried backlog from the failure
        # window drains again by the end of the day (continuous-time
        # recovery, not an idle-pool reset)
        assert w["sla_attainment"] > 0.85
        s = out.series["per_workload"]["dlrm-rmc1"]
        assert s["p95_ms"][-1] < max(s["p95_ms"][2:5])
        assert s["backlog_s"][-1] < max(s["backlog_s"][2:5])

    def test_transition_delay_gates_new_slots(self, small_cluster):
        """A growth step's added servers only serve after model_load_s: with
        an absurd load delay the measured window never sees them, yet
        make-before-break draining keeps the day feasible and in-SLA."""
        table, records, profiles, servers = small_cluster
        traces = _traces(table, 0.09, 12)
        R = max(load_increment_rate(t) for t in traces)
        out = _day(
            table, records, profiles, traces,
            servers=servers, overprovision=R,
            transitions=TransitionConfig(model_load_s=600.0, drain_s=700.0))
        assert out.feasible and out.all_meet_sla


class TestSeriesAndConservation:
    def test_series_schema_and_query_conservation(self, small_cluster):
        """The per-interval series is the Fig. 8b record: aligned with the
        trace, JSON-serializable, and query-conserving — every measured
        window accounts for its whole arrival stream exactly once through
        hysteresis holds, provisioning transitions and a mid-window
        machine failure (nothing lost, nothing double-served)."""
        import json

        table, records, profiles, servers = small_cluster
        traces = _traces(table, 0.09, 12)
        R = max(load_increment_rate(t) for t in traces)
        cfgt = TransitionConfig()
        out = _day(
            table, records, profiles, traces,
            servers=servers, overprovision=R,
            failures=[(3, 0, 0.4)], seed=0)
        assert any("failed" in e for e in out.events)
        T = traces.shape[1]
        assert out.series["interval_s"] == cfgt.interval_s
        for m, name in enumerate(table.workloads):
            s = out.series["per_workload"][name]
            for key in ("p50_ms", "p95_ms", "p99_ms", "sla_attainment",
                        "meets_sla", "n_queries", "backlog_s"):
                assert len(s[key]) == T, key
            expect = np.clip(traces[m] * cfgt.interval_s, 64,
                             1500).astype(int)
            assert s["n_queries"] == expect.tolist()
            assert sum(s["n_queries"]) == out.per_workload[name]["n_queries"]
            assert all(0.0 <= a <= 1.0 for a in s["sla_attainment"])
            assert all(b >= 0.0 for b in s["backlog_s"])
            assert 0.0 <= out.per_workload[name]["interval_sla_met_frac"] <= 1.0
        json.dumps(out.series)  # the bench writes this block verbatim


class TestEventCoreDay:
    """The batched event-ordered core (RuntimeConfig(event_core=True)):
    full-interval simulation, honest bridging flags, and bitwise agreement
    with the default path whenever the default's windows already cover
    their intervals."""

    @staticmethod
    def _flat_traces(table, qps, n_steps):
        M = len(table.workloads)
        return np.stack([diurnal_trace(qps, seed=m, n_steps=n_steps)
                         for m in range(M)])

    def test_bitwise_equal_when_windows_cover(self, small_cluster):
        """At a rate where the default path's windows span each interval
        uncapped (and with hedging suppressed), the event core must
        reproduce the default day bit for bit: same per-interval latency
        percentiles, query counts, attainment and power.  This pins the
        k==1-via-Lindley and fleet-kernel parity end to end through
        ``_finish_many``."""
        table, records, profiles, servers = small_cluster
        cfgt = TransitionConfig()
        # peak*interval under the default 1500-query window cap
        peak = 0.9 * 1500 / cfgt.interval_s
        traces = self._flat_traces(table, peak, 8)
        kw = dict(servers=servers, overprovision=0.3, seed=0)
        base = _day(
            table, records, profiles, traces, **kw,
            config=RuntimeConfig(hedge_factor=1e9))
        ev = _day(
            table, records, profiles, traces, **kw,
            config=RuntimeConfig(hedge_factor=1e9, event_core=True))
        assert base.peak_power_w == ev.peak_power_w
        for name in table.workloads:
            sb = base.series["per_workload"][name]
            se = ev.series["per_workload"][name]
            for key in ("p50_ms", "p95_ms", "p99_ms", "n_queries",
                        "sla_attainment", "backlog_s"):
                assert sb[key] == se[key], (name, key)
            assert not any(sb["bridged"])
            assert not any(se["bridged"])

    def test_full_interval_retires_the_bridge(self, small_cluster):
        """At benchmark load the default path caps each window at 1500
        queries and bridges the remainder by stationarity; the event core
        simulates every arrival of the interval and reports no bridging."""
        table, records, profiles, servers = small_cluster
        cfgt = TransitionConfig()
        # 40 qps: 24x the default 1500-query window, yet cheap to simulate
        traces = self._flat_traces(table, 40.0, 6)
        cap = 60_000
        assert float(traces.max()) * cfgt.interval_s < cap
        base = _day(table, records, profiles, traces,
                    servers=servers, overprovision=0.3, seed=0)
        ev = _day(
            table, records, profiles, traces,
            servers=servers, overprovision=0.3, seed=0,
            config=RuntimeConfig(event_core=True, event_core_queries=cap))
        assert ev.feasible
        for m, name in enumerate(table.workloads):
            sb = base.series["per_workload"][name]
            se = ev.series["per_workload"][name]
            assert any(sb["bridged"])          # default truncates + bridges
            assert not any(se["bridged"])      # event core covers the day
            expect = np.clip(traces[m] * cfgt.interval_s, 64, cap)
            assert se["n_queries"] == expect.astype(int).tolist()
            # provisioning decisions ride the same efficiency table
            assert base.peak_power_w == ev.peak_power_w
        assert ev.all_meet_sla, ev.per_workload

    def test_capped_event_day_stays_honest(self, small_cluster):
        """If event_core_queries still truncates the interval, the bridged
        flag must say so — the exactness claim is never silently faked."""
        table, records, profiles, servers = small_cluster
        traces = _traces(table, 0.09, 4)
        ev = _day(
            table, records, profiles, traces,
            servers=servers, overprovision=0.3, seed=0,
            config=RuntimeConfig(event_core=True, event_core_queries=2000))
        for name in table.workloads:
            se = ev.series["per_workload"][name]
            assert all(se["bridged"])
            assert se["n_queries"] == [2000] * traces.shape[1]

    def test_event_ordered_hedges_fire(self, small_cluster):
        """Full-interval populations surface real stragglers; the
        event-ordered pass admits their duplicates into live queues and
        the day still closes feasibly with sane latencies."""
        table, records, profiles, servers = small_cluster
        traces = _traces(table, 0.09, 6)
        ev = _day(
            table, records, profiles, traces,
            servers=servers, overprovision=0.3, seed=0,
            config=RuntimeConfig(event_core=True,
                                 event_core_queries=40_000))
        assert ev.feasible
        n_hedged = sum(w["n_hedged"] for w in ev.per_workload.values())
        assert n_hedged > 0
        for w in ev.per_workload.values():
            assert w["p99_ms"] > 0.0 and np.isfinite(w["p99_ms"])


class _StubService:
    """What the slot pool reads of a service: its rate and an idle state."""

    def __init__(self, qps):
        self.qps = qps

    def fresh_state(self):
        return {"pool": np.zeros(2)}


def _step(alloc, added, removed, coalloc=(), co_added=(), co_removed=()):
    return StatefulStep(
        alloc=np.array(alloc), added=np.array(added),
        removed=np.array(removed), capacity=0, power_w=0.0, feasible=True,
        resolved=False, coalloc=coalloc, co_added=co_added,
        co_removed=co_removed)


def _co(server, rate):
    return CoMachine(server=server, tenants=("a", "b"), rates=(rate, 1.0),
                     qps=(100.0, 100.0), dilation=(1.25, 1.25),
                     power_w=300.0)


class TestDayPhases:
    """The day loop's phases on hand-built intervals."""

    TR = TransitionConfig(model_load_s=120.0, drain_s=150.0)

    def test_slot_pool_carries_kept_and_gates_added(self):
        """Kept machines resume their carried (group, i) states, an added
        one starts idle once its model is loaded, a draining one inherits
        the next carried state and retires at the drain deadline; shared
        machines follow the solo ones with their machine identity."""
        svcs = {}

        def service(m, server, dilation=1.0):
            return svcs.setdefault((server, dilation),
                                   _StubService(10.0 * dilation))

        kept_c, added_c, drain_c = _co("T7", 4.0), _co("T7", 5.0), \
            _co("T7", 6.0)
        step = _step([[3], [0]], [[1], [0]], [[1], [0]],
                     coalloc=(added_c, kept_c), co_added=(added_c,),
                     co_removed=(drain_c,))
        g = ("c", "T7", ("a", "b"))
        prev = {(0, 0): {"pool": np.array([1.0, 2.0])},
                (0, 1): {"pool": np.array([3.0, 0.0])},
                (0, 2): {"pool": np.array([5.0, 4.0])},
                (g, 0): {"pool": np.array([7.0, 0.5])}}
        t0 = 900.0
        pool = _slot_pool(step, 0, "a", t0, self.TR, prev, service,
                          ("T2", "T3"))
        load, drain = t0 + 120.0, t0 + 150.0
        assert [s.server_type for s in pool.slots] == ["T2"] * 4 + ["T7"] * 3
        assert [s.ready_at for s in pool.slots] == \
            [t0, t0, load, t0, t0, load, t0]
        assert [s.retire_at for s in pool.slots] == \
            [np.inf] * 3 + [drain] + [np.inf] * 2 + [drain]
        assert [s.qps for s in pool.slots] == [10.0] * 4 + [4.0, 5.0, 6.0]
        assert [s.machine for s in pool.slots] == \
            [None] * 4 + [g + (0,), g + (1,), g + (2,)]
        assert pool.keys == [(0, 0), (0, 1), (0, 2), None,
                             (g, 0), (g, 1), None]
        want = [[1.0, 2.0], [3.0, 0.0], [0.0, 0.0], [5.0, 4.0],
                [7.0, 0.5], [0.0, 0.0], [0.0, 0.0]]
        for st, w in zip(pool.states, want):
            assert st["pool"].tolist() == (t0 + np.array(w)).tolist()
        assert pool.pair_of[4] is svcs[("T7", 1.25)]

    def test_carry_out_drops_failed_and_draining_and_compacts(self):
        """A machine failed mid-window and a draining one carry nothing
        out; the survivors' residual states move to compacted indices."""
        step = _step([[3]], [[0]], [[1]])
        prev = {(0, i): {"pool": np.array([float(i), 0.0])}
                for i in range(4)}
        pool = _slot_pool(step, 0, "a", 0.0, self.TR, prev,
                          lambda m, server, dilation=1.0: _StubService(1.0),
                          ("T2",))
        pool.states[1]["pool"] += 100.0      # served past the window end
        arrivals = np.linspace(0.0, 10.0, 11)
        fails = _retire_victims(pool, [(0, 0.5)], arrivals)
        assert fails == [(0, 5.0)]           # the first serving T2 died
        assert pool.slots[0].retire_at == 5.0
        carried, backlog = _carry_out(pool.states, pool.keys, 10.0)
        assert set(carried) == {(0, 0), (0, 1)}
        assert carried[(0, 0)]["pool"].tolist() == [91.0, 90.0]
        assert carried[(0, 1)]["pool"].tolist() == [0.0, 0.0]
        assert backlog == 91.0

    def test_tail_check_fails_on_a_lost_query(self):
        """One lost (infinite-latency) query fails the interval, even where
        the SLA quantile itself is finite and within the SLA."""
        ok = np.full(100, 0.001)
        lost = np.append(ok[:-1], np.inf)
        assert np.quantile(lost, 0.95) * 1e3 < 20.0
        assert _tail_met([ok, None], [20.0, 50.0], 0.95)
        assert not _tail_met([lost, None], [20.0, 50.0], 0.95)
        assert not _tail_met([ok, np.full(10, 0.06)], [20.0, 50.0], 0.95)
