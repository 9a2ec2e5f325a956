"""The declarative scenario zoo (`repro.serving.scenarios`).

Three layers, mirroring the module's contract:

- **scenario matrix** — parametrized over the *full registry* (not a
  hand-kept list), every registered scenario serves a smoke day and is
  pinned on feasibility, series schema, query conservation, and
  same-seed bit-identical replays.  A new scenario arrives pre-covered
  the moment it is registered.
- **golden equivalence** — the re-declared `baseline_day` / `failure_day`
  scenarios (and the example's customized failure day) reproduce the
  previously hand-wired `bench_cluster.py` / `examples/cluster_day.py`
  days bit-for-bit, so `BENCH_cluster.json` metrics are provably
  unchanged by the migration.
- **spec serialization** — `from_dict(to_dict(spec)) == spec` as a
  hypothesis property over generated specs, plus actionable rejection of
  unknown keys, unknown event kinds, malformed timelines and bad types.
"""
import dataclasses
import json
import pathlib
import tempfile

import numpy as np
import pytest

from repro.configs.paper_models import PAPER_MODELS, paper_profile
from repro.core import profile_cache
from repro.core.cluster import TransitionConfig
from repro.core.devices import SERVER_TYPES
from repro.core.efficiency import build_table
from repro.serving import scenarios as sc
from repro.serving.cluster_runtime import (
    DayInputs,
    RuntimeConfig,
    failure_schedule,
    simulate_cluster_day,
)
from repro.serving.diurnal import diurnal_trace, load_increment_rate
from repro.serving.scenarios import (
    EVENT_TYPES,
    SMOKE_AVAILABILITY,
    SMOKE_SERVERS,
    SMOKE_STEPS,
    SMOKE_WORKLOADS,
    Event,
    ScenarioError,
    ScenarioSpec,
    WorkloadSpec,
    compile_scenario,
    full_scale,
    get_scenario,
    register,
    registry,
    run_scenario,
)

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                   # dev-only dependency
    HAS_HYPOTHESIS = False


@pytest.fixture(scope="module", autouse=True)
def hermetic_profiles():
    """Profile into a throwaway cache and an empty bundle memo, so the
    suite neither reads nor pollutes `artifacts/profiles/` (and compiled
    tables cannot leak in from another test module)."""
    mp = pytest.MonkeyPatch()
    tmp = pathlib.Path(tempfile.mkdtemp())
    mp.setattr(profile_cache, "PROFILE_DIR", tmp)
    mp.setattr(sc, "_BUNDLES", {})
    mp.setattr(sc, "_COLOC_TABLES", {})
    yield
    mp.undo()


def _assert_day_equal(a, b, path=""):
    """Recursive bitwise equality over simulate_cluster_day outputs."""
    if isinstance(a, dict):
        assert isinstance(b, dict) and a.keys() == b.keys(), path
        for k in a:
            _assert_day_equal(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        assert np.array_equal(a, b), path
    elif isinstance(a, (list, tuple)):
        assert isinstance(b, (list, tuple)) and len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_day_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, float) and isinstance(b, float) \
            and np.isnan(a) and np.isnan(b):
        pass
    else:
        assert a == b, (path, a, b)


# ---------------------------------------------------------------------------
# the scenario matrix: every registered scenario, pinned automatically
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def zoo_days():
    """One compiled run per registered scenario (shared by the matrix)."""
    return {name: run_scenario(get_scenario(name)) for name in registry()}


class TestScenarioMatrix:
    def test_zoo_is_populated(self):
        """The registry carries the documented zoo, including the two
        golden re-declarations and the geo scenarios."""
        assert len(registry()) >= 9
        assert {"baseline_day", "failure_day", "geo_3region",
                "geo_partition", "geo_drain"} <= set(registry())

    @pytest.mark.parametrize("name", sorted(sc._REGISTRY))
    def test_scenario_smoke_day(self, name, zoo_days):
        """Feasibility + series schema + query conservation for every
        registered scenario — registration is the test plan."""
        spec = get_scenario(name)
        out = zoo_days[name]
        T = spec.n_steps
        if spec.regions is not None:
            # geo scenario: a GeoDayResult — one served day per region
            # plus origin-attributed SLA records (test_geo.py covers the
            # spill semantics; the matrix pins feasibility and schema)
            assert out.feasible, f"{name}: geo day infeasible"
            assert set(out.region_names) == {r.name for r in spec.regions}
            assert len(out.power) == T
            for rname in out.region_names:
                for wname, w in out.origin[rname].items():
                    assert 0.0 <= w["sla_attainment"] <= 1.0, (name, rname)
                    assert w["n_queries"] > 0, (name, rname, wname)
            json.dumps(out.to_dict())    # the bench writes this verbatim
            return
        assert out.feasible, f"{name}: day infeasible"
        assert out.series["interval_s"] > 0
        served = [w.name for w in spec.workloads
                  if w.name in out.series["per_workload"]]
        assert served, name
        for wname in served:
            s = out.series["per_workload"][wname]
            for key in ("p50_ms", "p95_ms", "p99_ms", "sla_attainment",
                        "meets_sla", "n_queries", "backlog_s", "bridged"):
                assert len(s[key]) == T, (name, wname, key)
            assert sum(s["n_queries"]) == \
                out.per_workload[wname]["n_queries"], (name, wname)
            assert all(0.0 <= a <= 1.0 for a in s["sla_attainment"]
                       if a is not None), (name, wname)
            assert all(b >= 0.0 for b in s["backlog_s"]), (name, wname)
        json.dumps(out.series)       # the bench writes this block verbatim

    @pytest.mark.parametrize("name", sorted(sc._REGISTRY))
    def test_scenario_deterministic(self, name, zoo_days):
        """Two independent compile+run passes are bit-identical — every
        source of randomness flows through seeds declared in the spec."""
        _assert_day_equal(zoo_days[name].to_dict(),
                          run_scenario(get_scenario(name)).to_dict())

    @pytest.mark.parametrize("name", sorted(sc._REGISTRY))
    def test_scenario_round_trips(self, name):
        """Every registered spec survives a JSON round trip exactly."""
        spec = get_scenario(name)
        assert ScenarioSpec.from_dict(
            json.loads(json.dumps(spec.to_dict()))) == spec


# ---------------------------------------------------------------------------
# golden equivalence: the re-declared days == the hand-wired days
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hand_wired():
    """The exact pre-refactor wiring of bench_cluster.py --smoke /
    examples/cluster_day.py --smoke, kept verbatim as the oracle."""
    profiles = {n: paper_profile(n) for n in ("dlrm-rmc1", "dlrm-rmc3")}
    servers = {s: SERVER_TYPES[s] for s in ("T2", "T3", "T7")}
    table, records = build_table(profiles, servers,
                                 {"T2": 70, "T3": 15, "T7": 5})
    cap = (table.avail[:, None] * table.qps).sum(axis=0)
    traces = np.stack([diurnal_trace(0.09 * cap[m], seed=m, n_steps=24)
                       for m in range(len(table.workloads))])
    R = max(load_increment_rate(t) for t in traces)
    return table, records, profiles, servers, traces, R


class TestGoldenEquivalence:
    @pytest.mark.parametrize("policy", ["greedy", "hercules"])
    def test_baseline_day_matches_bench_wiring(self, hand_wired, policy):
        """The registered baseline_day == bench_cluster.py's runtime
        validation day, bit for bit (so BENCH_cluster*.json is pinned)."""
        table, records, profiles, servers, traces, R = hand_wired
        ref = simulate_cluster_day(
            DayInputs(table=table, records=records, profiles=profiles,
                      traces=traces, servers=servers, overprovision=R,
                      transitions=TransitionConfig()),
            policy=policy)
        comp = compile_scenario(get_scenario("baseline_day"))
        assert np.array_equal(comp.traces, traces)
        assert comp.overprovision == R
        _assert_day_equal(ref.to_dict(), comp.run(policy=policy).to_dict())

    def test_failure_day_matches_bench_wiring(self, hand_wired):
        """The registered failure_day == bench_cluster.py's fault-tolerance
        day (failure_schedule fail_prob=0.01 seed=7)."""
        table, records, profiles, servers, traces, R = hand_wired
        fails = failure_schedule(traces.shape[1], len(table.servers),
                                 fail_prob=0.01, seed=7)
        ref = simulate_cluster_day(
            DayInputs(table=table, records=records, profiles=profiles,
                      traces=traces, servers=servers, overprovision=R,
                      transitions=TransitionConfig(), failures=fails))
        comp = compile_scenario(get_scenario("failure_day"))
        assert comp.failures == fails
        _assert_day_equal(ref.to_dict(), comp.run().to_dict())

    def test_example_day_matches_example_wiring(self, hand_wired):
        """examples/cluster_day.py's customized failure day (2% / seed 0,
        including the --event-core re-serve) == the old hand wiring."""
        table, records, profiles, _, traces, R = hand_wired
        fails = failure_schedule(traces.shape[1], len(table.servers),
                                 fail_prob=0.02, seed=0)
        day = dataclasses.replace(
            get_scenario("failure_day"),
            events=(Event.create("random_failures", fail_prob=0.02,
                                 seed=0),))
        inputs = DayInputs(table=table, records=records, profiles=profiles,
                           traces=traces, overprovision=R,
                           transitions=TransitionConfig(), failures=fails)
        ref = simulate_cluster_day(inputs)
        _assert_day_equal(ref.to_dict(), run_scenario(day).to_dict())
        cap = 20_000
        ref_exact = simulate_cluster_day(
            inputs,
            config=RuntimeConfig(event_core=True, event_core_queries=cap))
        exact = run_scenario(dataclasses.replace(
            day, runtime={"event_core": True, "event_core_queries": cap}))
        _assert_day_equal(ref_exact.to_dict(), exact.to_dict())


# ---------------------------------------------------------------------------
# golden days: every registered day and one event-core day, pinned in JSON
# ---------------------------------------------------------------------------
#
# Regenerate only when a day's output is meant to change:
#   PYTHONPATH=src:tests JAX_PLATFORMS=cpu python -c \
#       "import test_scenarios as t; t.write_golden_days()"

GOLDEN_DAYS = pathlib.Path(__file__).with_name("golden_days.json")
EVENT_CORE_DAY = "event_core_hedges"


def _event_core_day():
    """TestEventCoreDay's hedging day (tests/test_cluster_runtime.py): the
    smoke topology at 9% load over 6 intervals, every arrival served and
    stragglers hedged event-ordered."""
    profiles = {n: paper_profile(n) for n in SMOKE_WORKLOADS}
    servers = {s: SERVER_TYPES[s] for s in SMOKE_SERVERS}
    table, records = build_table(profiles, servers, dict(SMOKE_AVAILABILITY))
    cap = table.fleet_capacity()
    traces = np.stack([diurnal_trace(0.09 * cap[m], seed=m, n_steps=6)
                       for m in range(len(table.workloads))])
    return simulate_cluster_day(
        DayInputs(table=table, records=records, profiles=profiles,
                  traces=traces, servers=servers, overprovision=0.3, seed=0),
        config=RuntimeConfig(event_core=True, event_core_queries=40_000))


def _day_record(out) -> dict:
    """A served day as JSON: ``to_dict()``, which holds the ``series``
    block and the ``events``; a geo day adds each region's whole day."""
    rec = {"day": out.to_dict()}
    if hasattr(out, "regions"):
        rec["regions"] = {n: r.to_dict() for n, r in out.regions.items()}
    return json.loads(json.dumps(
        rec, default=lambda o: o.tolist() if hasattr(o, "tolist") else o))


def _assert_matches_golden(got, want, path="day"):
    """Ints, bools, strings, None and events exactly; floats to rtol 1e-9."""
    if isinstance(want, dict):
        assert isinstance(got, dict) and got.keys() == want.keys(), path
        for k in want:
            _assert_matches_golden(got[k], want[k], f"{path}.{k}")
    elif isinstance(want, list):
        assert isinstance(got, list) and len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_matches_golden(g, w, f"{path}[{i}]")
    elif isinstance(want, float):
        assert isinstance(got, float), (path, got, want)
        assert got == want or (np.isnan(got) and np.isnan(want)) \
            or np.isclose(got, want, rtol=1e-9, atol=0.0), (path, got, want)
    else:
        assert type(got) is type(want) and got == want, (path, got, want)


def write_golden_days(path=GOLDEN_DAYS):
    """Serve every golden day in a throwaway profile cache and write them."""
    mp = pytest.MonkeyPatch()
    mp.setattr(profile_cache, "PROFILE_DIR", pathlib.Path(tempfile.mkdtemp()))
    mp.setattr(sc, "_BUNDLES", {})
    mp.setattr(sc, "_COLOC_TABLES", {})
    try:
        days = {n: _day_record(run_scenario(get_scenario(n)))
                for n in registry()}
        days[EVENT_CORE_DAY] = _day_record(_event_core_day())
    finally:
        mp.undo()
    path.write_text(json.dumps(days, indent=1, sort_keys=True) + "\n")


@pytest.mark.parametrize("name", sorted(sc._REGISTRY) + [EVENT_CORE_DAY])
def test_day_matches_golden(name, zoo_days):
    """Every registered day, and an event-core day with hedges, reproduce
    the recorded output: the serving refactors must not move a number."""
    want = json.loads(GOLDEN_DAYS.read_text())
    assert set(want) == set(registry()) | {EVENT_CORE_DAY}
    out = _event_core_day() if name == EVENT_CORE_DAY else zoo_days[name]
    _assert_matches_golden(_day_record(out), want[name])


# ---------------------------------------------------------------------------
# spec construction, registry, and full_scale
# ---------------------------------------------------------------------------


def _spec(**kw):
    base = dict(
        name="t",
        workloads=(WorkloadSpec("dlrm-rmc1"),
                   WorkloadSpec("dlrm-rmc3", trace_seed=1)),
        servers=SMOKE_SERVERS,
        availability=dict(SMOKE_AVAILABILITY),
        n_steps=SMOKE_STEPS,
    )
    base.update(kw)
    return ScenarioSpec(**base)


class TestSpecValidation:
    def test_rejects_unknown_workload(self):
        with pytest.raises(ScenarioError, match="unknown workload"):
            _spec(workloads=(WorkloadSpec("not-a-model"),))

    def test_rejects_duplicate_workloads(self):
        with pytest.raises(ScenarioError, match="duplicate workload"):
            _spec(workloads=(WorkloadSpec("dlrm-rmc1"),
                             WorkloadSpec("dlrm-rmc1")))

    def test_rejects_unknown_server(self):
        with pytest.raises(ScenarioError, match="unknown server type"):
            _spec(servers=("T2", "T99"), availability=None)

    def test_rejects_availability_outside_pool(self):
        with pytest.raises(ScenarioError, match="not in the pool"):
            _spec(availability={"T2": 70, "T10": 3})

    def test_rejects_unknown_policy(self):
        with pytest.raises(ScenarioError, match="unknown policy"):
            _spec(policy="magic")

    def test_rejects_short_day(self):
        with pytest.raises(ScenarioError, match="n_steps"):
            _spec(n_steps=1)

    def test_rejects_unknown_runtime_key(self):
        with pytest.raises(ScenarioError, match="hedge_quantile"):
            _spec(runtime={"hedge_quantil": 0.9})     # typo'd key

    def test_rejects_mistyped_transitions(self):
        with pytest.raises(ScenarioError, match="drain_s"):
            _spec(transitions={"drain_s": "fast"})

    def test_rejects_unknown_event_kind(self):
        with pytest.raises(ScenarioError, match="unknown event kind"):
            Event.create("earthquake", at=3)

    def test_rejects_missing_event_field(self):
        with pytest.raises(ScenarioError, match="missing required field"):
            Event.create("load_surge", start=1, end=3)   # no factor

    def test_rejects_unknown_event_field(self):
        with pytest.raises(ScenarioError, match="unknown key"):
            Event.create("model_push", workload="din", at=3, rampp=2)

    def test_rejects_out_of_range_window(self):
        with pytest.raises(ScenarioError, match="outside the day"):
            _spec(events=(Event.create("load_surge", start=4, end=99,
                                       factor=1.2),))

    def test_rejects_event_referencing_absent_workload(self):
        with pytest.raises(ScenarioError, match="not in this scenario"):
            _spec(events=(Event.create("model_push", workload="din",
                                       at=3),))

    def test_rejects_event_referencing_absent_server(self):
        with pytest.raises(ScenarioError, match="not in this scenario"):
            _spec(events=(Event.create("machine_failure", at=3,
                                       server="T10"),))

    def test_event_defaults_filled(self):
        ev = Event.create("model_push", workload="din", at=3)
        assert ev.params["ramp"] == 1
        assert ev.params["canary_frac"] == pytest.approx(0.02)

    def test_from_dict_rejects_unknown_spec_key(self):
        d = get_scenario("baseline_day").to_dict()
        d["n_stepz"] = 12
        with pytest.raises(ScenarioError, match="n_stepz"):
            ScenarioSpec.from_dict(d)

    def test_from_dict_rejects_malformed_timeline(self):
        d = get_scenario("baseline_day").to_dict()
        d["events"] = [{"at": 3}]                     # event without a kind
        with pytest.raises(ScenarioError, match="missing 'kind'"):
            ScenarioSpec.from_dict(d)

    def test_error_messages_name_the_alternatives(self):
        """Rejections must be actionable: they name what would be valid."""
        with pytest.raises(ScenarioError, match="dlrm-rmc1"):
            _spec(workloads=(WorkloadSpec("nope"),))
        with pytest.raises(ScenarioError, match="load_surge"):
            Event.create("surge", start=1, end=2, factor=2.0)
        with pytest.raises(ScenarioError, match="baseline_day"):
            get_scenario("no-such-scenario")


class TestRegistry:
    def test_register_rejects_duplicates_unless_replace(self):
        spec = _spec(name="baseline_day")
        with pytest.raises(ScenarioError, match="already registered"):
            register(spec)

    def test_register_and_replace(self):
        spec = _spec(name="tmp-registry-probe")
        try:
            register(spec)
            assert get_scenario("tmp-registry-probe") == spec
            spec2 = dataclasses.replace(spec, n_steps=12)
            register(spec2, replace=True)
            assert get_scenario("tmp-registry-probe").n_steps == 12
        finally:
            sc._REGISTRY.pop("tmp-registry-probe", None)
        assert "tmp-registry-probe" not in registry()


class TestFullScale:
    def test_full_scale_structure(self):
        """full_scale lifts to the whole paper zoo with benchmark trace
        seeding and proportionally rescaled event intervals — without
        profiling anything (structure only; the full table is a bench
        concern)."""
        spec = full_scale(get_scenario("flash_crowd"), n_steps=96)
        assert spec.workload_names() == tuple(PAPER_MODELS)
        assert [w.trace_seed for w in spec.workloads] == list(range(6))
        assert spec.servers is None and spec.availability is None
        assert spec.n_steps == 96
        (ev,) = spec.events
        base = get_scenario("flash_crowd").events[0]
        scale = 96 / SMOKE_STEPS
        assert ev.params["start"] == round(base.params["start"] * scale)
        assert ev.params["end"] == round(base.params["end"] * scale)
        assert ev.params["factor"] == base.params["factor"]

    def test_full_scale_keeps_load_frac(self):
        spec = full_scale(get_scenario("baseline_day"))
        assert all(w.load_frac == pytest.approx(sc.COMPARISON_FRAC)
                   for w in spec.workloads)


# ---------------------------------------------------------------------------
# hypothesis: serialization round trip over generated specs
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    _frac = st.floats(0.01, 0.5, allow_nan=False, allow_infinity=False)
    _hour = st.floats(0.0, 24.0, allow_nan=False, allow_infinity=False)

    _workloads = st.lists(
        st.sampled_from(sorted(PAPER_MODELS)), min_size=1, max_size=3,
        unique=True,
    ).flatmap(lambda names: st.tuples(*[
        st.builds(WorkloadSpec, name=st.just(n), load_frac=_frac,
                  trace_seed=st.integers(0, 99), peak_hour=_hour,
                  shoulder_hour=_hour,
                  valley_frac=st.floats(0.0, 0.9, allow_nan=False),
                  jitter=st.floats(0.0, 0.1, allow_nan=False))
        for n in names]))

    def _events_for(spec: ScenarioSpec):
        names = st.sampled_from(list(spec.workload_names()))
        lo = st.integers(0, spec.n_steps - 2)
        window = st.tuples(lo, st.integers(1, 4)).map(
            lambda se: (se[0], min(se[0] + se[1], spec.n_steps)))
        surge = window.flatmap(lambda w: st.builds(
            Event.create, st.just("load_surge"), start=st.just(w[0]),
            end=st.just(w[1]), factor=st.floats(0.5, 2.0, allow_nan=False),
            workload=st.none() | names))
        push = st.builds(
            Event.create, st.just("model_push"), workload=names,
            at=lo, ramp=st.integers(1, 4),
            canary_frac=st.floats(0.0, 0.5, allow_nan=False,
                                  exclude_max=True))
        fail = st.builds(
            Event.create, st.just("random_failures"),
            fail_prob=st.floats(0.0, 0.2, allow_nan=False),
            seed=st.integers(0, 99))
        return st.lists(surge | push | fail, max_size=3).map(tuple)

    _specs = st.builds(
        ScenarioSpec,
        name=st.text(
            alphabet="abcdefghijklmnopqrstuvwxyz-", min_size=1, max_size=16),
        description=st.just(""),
        workloads=_workloads,
        servers=st.just(SMOKE_SERVERS),
        availability=st.just(dict(SMOKE_AVAILABILITY)) | st.none(),
        n_steps=st.integers(4, 48),
        seed=st.integers(0, 99),
        overprovision=st.none() | st.floats(0.0, 1.0, allow_nan=False),
        policy=st.sampled_from(["nh", "greedy", "hercules"]),
        runtime=st.just({}) | st.just({"hedge_quantile": 0.9}),
        transitions=st.just({}) | st.just({"hysteresis": 0.2}),
    ).flatmap(lambda s: _events_for(s).map(
        lambda evs: dataclasses.replace(s, events=evs)))

    @settings(max_examples=60, deadline=None)
    @given(spec=_specs)
    def test_spec_json_round_trip(spec):
        """from_dict(to_dict(spec)) == spec, through real JSON text."""
        assert ScenarioSpec.from_dict(
            json.loads(json.dumps(spec.to_dict()))) == spec

    @settings(max_examples=30, deadline=None)
    @given(spec=_specs, key=st.sampled_from(
        ["n_stepz", "workload", "extra", "oversubscription"]))
    def test_unknown_spec_keys_rejected(spec, key):
        d = spec.to_dict()
        d[key] = 1
        with pytest.raises(ScenarioError, match="unknown key"):
            ScenarioSpec.from_dict(d)

    @settings(max_examples=30, deadline=None)
    @given(spec=_specs, data=st.data())
    def test_malformed_event_timelines_rejected(spec, data):
        d = spec.to_dict()
        bad = data.draw(st.sampled_from([
            {"kind": "not-an-event", "at": 1},
            {"kind": "load_surge", "start": 0},        # missing end/factor
            {"kind": "machine_failure", "at": 0, "server": "T2",
             "window_frac": "half"},                   # wrong type
        ]))
        d["events"] = list(d["events"]) + [bad]
        with pytest.raises(ScenarioError):
            ScenarioSpec.from_dict(d)
else:  # pragma: no cover - exercised only without the dev deps
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_spec_json_round_trip():
        pass
