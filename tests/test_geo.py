"""Geo-distributed serving (`repro.serving.geo`): spill-plan conservation
and limits, origin attribution with the link RTT added exactly once,
partition/drain event semantics, the follow-the-sun power win over the
per-region-isolated baseline, and spec serialization."""
import json
import pathlib
import tempfile

import numpy as np
import pytest

from repro.core import profile_cache
from repro.serving import scenarios as sc
from repro.serving.geo import GeoConfig, plan_spill
from repro.serving.router import split_stream_by_share
from repro.serving.scenarios import (
    ScenarioSpec,
    compile_scenario,
    get_scenario,
)


@pytest.fixture(scope="module", autouse=True)
def hermetic_profiles():
    mp = pytest.MonkeyPatch()
    tmp = pathlib.Path(tempfile.mkdtemp())
    mp.setattr(profile_cache, "PROFILE_DIR", tmp)
    mp.setattr(sc, "_BUNDLES", {})
    yield
    mp.undo()


@pytest.fixture(scope="module")
def geo3():
    return compile_scenario(get_scenario("geo_3region"))


@pytest.fixture(scope="module")
def fs(geo3):
    return geo3.run(mode="follow_sun")


@pytest.fixture(scope="module")
def iso(geo3):
    return geo3.run(mode="isolated")


def _loads(comp):
    names = comp.region_names
    return np.stack([np.asarray(comp.days[n].traces, float) for n in names])


def _flows(comp, plan):
    """[R, M, T] planned outflow / inflow from a spill plan."""
    loads = _loads(comp)
    R, M, T = loads.shape
    out = np.zeros((R, M, T))
    inc = np.zeros((R, M, T))
    for t, sp in enumerate(plan):
        for (i, j), s in sorted(sp.items()):
            out[i, :, t] += s
            inc[j, :, t] += s
    return loads, out, inc


class TestSpillPlan:
    def test_conserves_and_respects_limits(self, geo3):
        """No region ships more than its offered load, no link carries more
        than its capacity, every spilled workload fits the RTT budget, and
        globally served == offered (nothing lost without a drain)."""
        plan, events, ok = plan_spill(geo3)
        assert ok, events
        loads, out, inc = _flows(geo3, plan)
        net = geo3.network
        days = [geo3.days[n] for n in geo3.region_names]
        slas = np.array([days[0].profiles[w].sla_ms
                         for w in days[0].table.workloads])
        for t, sp in enumerate(plan):
            for (i, j), s in sp.items():
                assert (s >= 0.0).all(), (t, (i, j))
                # RTT budget: spill only where rtt <= 0.5 * SLA
                spilled = s > 0.0
                assert (net.rtt_ms[(i, j)] <=
                        GeoConfig().rtt_budget_frac * slas[spilled]).all(), \
                    (t, (i, j), s)
                assert float(s.sum()) <= net.cap_qps[(i, j)] + 1e-6, (t, i, j)
            # per-origin: outflow never exceeds offered load
            assert (out[:, :, t] <= loads[:, :, t] + 1e-6).all(), t
        # conservation: served == offered globally, per (workload, interval)
        served = loads - out + inc
        np.testing.assert_allclose(served.sum(axis=0), loads.sum(axis=0),
                                   rtol=1e-9, atol=1e-6)
        assert float(out.sum()) > 0.0     # the plan actually spills

    def test_rmc1_never_crosses_the_long_link(self, geo3):
        """dlrm-rmc1 (20 ms SLA, 10 ms budget) must not spill over the
        12 ms eu-west<->ap-south link in either direction."""
        names = list(geo3.region_names)
        eu, ap = names.index("eu-west"), names.index("ap-south")
        m1 = list(geo3.days[names[0]].table.workloads).index("dlrm-rmc1")
        plan, _, _ = plan_spill(geo3)
        for t, sp in enumerate(plan):
            for p in ((eu, ap), (ap, eu)):
                if p in sp:
                    assert sp[p][m1] == 0.0, (t, p)

    def test_greedy_placement_also_conserves(self, geo3):
        plan, events, ok = plan_spill(geo3, GeoConfig(placement="greedy"))
        assert ok, events
        loads, out, inc = _flows(geo3, plan)
        np.testing.assert_allclose((loads - out + inc).sum(axis=0),
                                   loads.sum(axis=0), rtol=1e-9, atol=1e-6)

    def test_unknown_placement_rejected(self, geo3):
        with pytest.raises(ValueError, match="placement"):
            plan_spill(geo3, GeoConfig(placement="magic"))


class TestOriginAttribution:
    def test_rtt_added_exactly_once(self, geo3, fs):
        """Recompute one origin's attributed latency pool independently
        from the plan + each destination's measured stream: local shares
        carry no RTT, remote shares carry exactly one link RTT.  The
        result's origin percentiles must match bit for bit."""
        names = list(fs.region_names)
        R = len(names)
        plan, _, _ = plan_spill(geo3)
        loads, out_, inc = _flows(geo3, plan)
        _, M, T = loads.shape
        served = loads - out_ + inc
        served[served < 1e-6] = 0.0     # mirror simulate_geo_day's clamp
        wl = geo3.days[names[0]].table.workloads
        i0 = 0                                     # origin under test
        for m, wname in enumerate(wl):
            pool = []
            n_spilled = 0
            for j in range(R):
                lats = fs.regions[names[j]].latencies
                for t in range(T):
                    lat = None if lats is None else lats[m][t]
                    if lat is None or len(lat) == 0:
                        continue
                    shares = np.zeros(R)
                    shares[j] = max(
                        float(served[j, m, t] - inc[j, m, t]), 0.0)
                    for (i, j2), s in plan[t].items():
                        if j2 == j:
                            shares[i] += s[m]
                    if shares.sum() <= 0.0:
                        shares[j] = 1.0
                    assign = split_stream_by_share(
                        len(lat), shares, seq=(j * M + m) * T + t)
                    sel = lat[assign == i0]
                    if len(sel) == 0:
                        continue
                    if i0 != j:
                        rtt_s = geo3.network.rtt_ms[(i0, j)] / 1e3
                        sel = sel + rtt_s
                        n_spilled += len(sel)
                        # one RTT is a hard floor on a spilled latency
                        assert float(sel.min()) >= rtt_s
                    pool.append(sel)
            lat_ms = np.concatenate(pool) * 1e3
            got = fs.origin[names[i0]][wname]
            assert got["n_spilled"] == n_spilled
            assert got["p99_ms"] == float(np.percentile(lat_ms, 99))
            assert got["n_queries"] == len(lat_ms)

    def test_every_origin_measured(self, fs):
        for rname in fs.region_names:
            for w in fs.origin[rname].values():
                assert w["n_queries"] > 0
                assert np.isfinite(w["p99_ms"])


class TestFollowTheSun:
    def test_beats_isolated_on_global_peak_power(self, fs, iso):
        """The headline: phase-shifted peaks + spill de-synchronize the
        global fleet peak — strictly less provisioned peak power than
        per-region-isolated Hercules, with every SLA met."""
        assert fs.feasible and iso.feasible
        assert fs.peak_power_w < iso.peak_power_w
        assert fs.all_meet_sla and fs.all_intervals_meet_sla
        assert fs.n_spilled > 0 and iso.n_spilled == 0
        assert fs.lost_qps_mean == 0.0

    def test_isolated_shares_region_days(self, fs, iso):
        """Both modes provision from the same base-curve over-provision
        rate; isolated regions see exactly the offered load."""
        assert fs.region_names == iso.region_names
        for name in iso.region_names:
            assert iso.regions[name].feasible

    def test_to_dict_json_safe(self, fs):
        d = json.loads(json.dumps(fs.to_dict()))
        assert d["mode"] == "follow_sun"
        assert len(d["power_w"]) == len(fs.power)
        assert d["peak_power_w"] == fs.peak_power_w


class TestGeoEvents:
    def test_partition_forces_local_only(self):
        """During the partition window no planned flow touches the severed
        region in either direction."""
        comp = compile_scenario(get_scenario("geo_partition"))
        assert comp.partitions, "geo_partition must register a partition"
        (rname, start, end) = comp.partitions[0]
        sev = list(comp.region_names).index(rname)
        plan, _, ok = plan_spill(comp)
        assert ok
        for t in range(start, end):
            for (i, j) in plan[t]:
                assert sev not in (i, j), (t, (i, j))
        # outside the window the region participates again
        participates = [
            t for t, sp in enumerate(plan)
            if any(sev in p for p in sp)
        ]
        assert any(t < start or t >= end for t in participates)

    def test_drain_evacuates_make_before_break(self):
        """geo_drain: follow-the-sun places the evacuated load on the
        surviving regions (nothing lost, SLAs met); isolated has nowhere
        to put it and reports the load lost."""
        comp = compile_scenario(get_scenario("geo_drain"))
        assert comp.drains
        (rname, at, ramp) = comp.drains[0]
        fs_d = comp.run(mode="follow_sun")
        assert fs_d.feasible and fs_d.all_meet_sla
        assert fs_d.lost_qps_mean == 0.0
        assert fs_d.n_spilled > 0
        # the drained region's fleet ramps to zero load after the window
        drained = fs_d.regions[rname]
        assert drained.capacity[-1] < drained.capacity[0]
        iso_d = comp.run(mode="isolated")
        assert iso_d.lost_qps_mean > 0.0
        assert not iso_d.feasible


class TestGeoSpecSerialization:
    @pytest.mark.parametrize(
        "name", ["geo_3region", "geo_partition", "geo_drain"])
    def test_round_trip(self, name):
        spec = get_scenario(name)
        back = ScenarioSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert back == spec
        assert back.regions == spec.regions
        assert back.links == spec.links


class TestWithAvailability:
    def test_rebinds_pool_without_reprofiling(self, geo3):
        table = geo3.days["us-east"].table
        new = {s: 1 for s in table.servers}
        t2 = table.with_availability(new)
        assert (t2.avail == 1).all()
        assert np.array_equal(t2.qps, table.qps)
        assert np.array_equal(t2.power, table.power)
        assert (table.avail != 1).any()    # original untouched

    def test_missing_type_rejected(self, geo3):
        table = geo3.days["us-east"].table
        with pytest.raises(KeyError, match=table.servers[0]):
            table.with_availability({})
