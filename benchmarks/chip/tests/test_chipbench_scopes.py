"""Device time by the program's scope names: the scope map of a compiled
step, its reduction over a synthetic trace and the readers of scopes named
by the configuration, the idle split, the tiny DLRM step's scopes, and the
probe's readings on a tiny CPU run and its refusal without a chip."""
from __future__ import annotations

import os
import subprocess
import sys
import time
import types

import pytest

from chipbench_tiny import BENCH, D, ROOT, dlrm_family, harness, tiny_cell, tiny_program
from chipbench import readings, scopes, tracing, traffic

RMC1_SCOPES = harness.load_json(BENCH / "configs" / "dlrm-rmc1.json")["scopes"]

# A compiled step's HLO text as the TPU compiler prints it (metadata cut to
# ``op_name``): fused computations first, then the entry computation.
HLO = r"""HloModule jit_serve_step, is_scheduled=true, entry_computation_layout={(f32[10,32]{1,0})->f32[4]{0}}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%fused_gather (param_0: f32[10,32], param_1: s32[320]) -> f32[320,32] {
  %param_0 = f32[10,32]{1,0} parameter(0)
  %param_1 = s32[320]{0} parameter(1)
  %custom-call.3 = f32[320,32]{1,0} custom-call(%param_0, %param_1), metadata={op_name="jit(serve_step)/sparse/gather/jit(_take)/gather"}
  ROOT %reshape.5 = f32[320,32]{1,0} reshape(%custom-call.3), metadata={op_name="reduce_window_sum"}
}

%fused_pool (param_0: f32[4,10,8,32], param_1: f32[4,10,8]) -> f32[4,10,32] {
  %param_0 = f32[4,10,8,32]{3,2,1,0} parameter(0)
  %param_1 = f32[4,10,8]{2,1,0} parameter(1)
  %constant.1 = f32[] constant(0), metadata={op_name="jit(serve_step)/sparse/gather/jit(_take)"}
  %select_n.1 = f32[4,10,8,32]{3,2,1,0} select(%param_0, %param_0, %param_0), metadata={op_name="jit(serve_step)/sparse/gather/jit(_take)/select_n"}
  %mul.1 = f32[4,10,8,32]{3,2,1,0} multiply(%select_n.1, %param_1), metadata={op_name="jit(serve_step)/sparse/pool/mul"}
  ROOT %reduce_sum.1 = f32[4,10,32]{2,1,0} reduce(%mul.1, %constant.1), dimensions={2}, to_apply=%add, metadata={op_name="jit(serve_step)/sparse/pool/reduce_sum"}
}

%fused_relu (param_0: f32[4,128]) -> f32[4,128] {
  %param_0 = f32[4,128]{1,0} parameter(0)
  ROOT %max.2 = f32[4,128]{1,0} maximum(%param_0, %param_0), metadata={op_name="jit(serve_step)/dense/mlp/jit(relu)/max"}
}

%fused_bottom (param_0: f32[4,13], param_1: f32[13,128]) -> f32[4,128] {
  %param_0 = f32[4,13]{1,0} parameter(0)
  %param_1 = f32[13,128]{1,0} parameter(1)
  %dot.1 = f32[4,128]{1,0} dot(%param_0, %param_1), metadata={op_name="jit(serve_step)/dense/mlp/dot_general"}
  ROOT %fusion.8 = f32[4,128]{1,0} fusion(%dot.1), kind=kLoop, calls=%fused_relu, metadata={op_name="jit(serve_step)/dense/mlp/jit(relu)/max"}
}

%fused_top (param_0: f32[4,32], param_1: f32[4,55]) -> f32[4] {
  %param_0 = f32[4,32]{1,0} parameter(0)
  %param_1 = f32[4,55]{1,0} parameter(1)
  %concatenate.2 = f32[4,87]{1,0} concatenate(%param_0, %param_1), dimensions={1}, metadata={op_name="jit(serve_step)/dense/interaction/concatenate"}
  ROOT %dot.2 = f32[4]{0} dot(%concatenate.2, %concatenate.2), metadata={op_name="jit(serve_step)/dense/mlp/dot_general"}
}

%fused_cross (param_0: f32[4,10,32], param_1: f32[4,128]) -> f32[4,128] {
  %param_0 = f32[4,10,32]{2,1,0} parameter(0)
  %param_1 = f32[4,128]{1,0} parameter(1)
  %convert.4 = f32[4,10,32]{2,1,0} convert(%param_0), metadata={op_name="jit(serve_step)/sparse/pool/convert_element_type"}
  ROOT %add.4 = f32[4,128]{1,0} add(%param_1, %param_1), metadata={op_name="jit(serve_step)/dense/mlp/add"}
}

ENTRY %main.22 (p: f32[10,32]) -> f32[4] {
  %p = f32[10,32]{1,0} parameter(0), metadata={op_name="p[\'embedding\'][\'table\']"}
  %ids = s32[320]{0} parameter(1), metadata={op_name="b[\'sparse_ids\']"}
  %fusion.1 = f32[320,32]{1,0} fusion(%p, %ids), kind=kCustom, calls=%fused_gather, metadata={op_name="jit(serve_step)/sparse/gather/jit(_take)/gather"}
  %fusion.3 = f32[4,10,32]{2,1,0} fusion(%fusion.1, %p), kind=kLoop, calls=%fused_pool, metadata={op_name="jit(serve_step)/sparse/pool/reduce_sum"}
  %gather.7 = s32[55]{0} gather(%ids, %ids), metadata={op_name="jit(serve_step)/dense/interaction/gather"}
  %fusion.9 = f32[4,128]{1,0} fusion(%p, %p), kind=kOutput, calls=%fused_bottom, metadata={op_name="jit(serve_step)/dense/mlp/jit(relu)/max"}
  %fusion.12 = f32[4,128]{1,0} fusion(%fusion.3, %fusion.9), kind=kLoop, calls=%fused_cross, metadata={op_name="jit(serve_step)/dense/mlp/add"}
  %fusion.27 = f32[4]{0} fusion(%p, %p), kind=kOutput, calls=%fused_top, metadata={op_name="jit(serve_step)/dense/mlp/dot_general"}
  %reduce-window = s32[121,1]{1,0} reduce-window(%ids, %ids), window={size=121x1}, to_apply=%add
  %copy.9 = f32[4]{0} copy(%fusion.27), metadata={op_name="jit(serve_step)/copy"}
  %copy-start = (f32[4]{0}) copy-start(%copy.9)
  ROOT %tuple.3 = (f32[4]{0}) tuple(%copy.9)
}
"""


@pytest.mark.parametrize("op, path", [
    ("fusion.1", "sparse/gather"),      # a leaf scope, its made-up root name aside
    ("fusion.3", "sparse"),             # gather's select fused into the pool
    ("gather.7", "dense/interaction"),  # a ``gather`` primitive outside G_s
    ("fusion.9", "dense/mlp"),          # through a fusion nested in a fusion
    ("fusion.27", "dense"),             # interaction output fused into the top MLP
    ("fusion.12", ""),                  # G_s and G_d in one fusion
    ("reduce-window", ""),              # no metadata
    ("copy.9", ""),                     # traced, under no scope
    ("p", ""),                          # a parameter's name
])
@pytest.mark.parametrize("names", [RMC1_SCOPES, None])
def test_op_scopes(op, path, names):
    # None keeps every component but a transformation's (``jit(_take)``)
    assert scopes.op_scopes(HLO, names)[op] == path


def test_op_scopes_maps_the_entry_computation_only():
    got = scopes.op_scopes(HLO, RMC1_SCOPES)
    assert {"mul.1", "custom-call.3", "sum"}.isdisjoint(got)
    assert got["copy-start"] == got["tuple.3"] == ""
    # names outside the list are not scopes: without ``gather`` the gather
    # fusion is plain ``sparse``
    assert scopes.op_scopes(HLO, ("sparse", "dense"))["fusion.1"] == "sparse"


def _trace():
    """Two step runs in a 10 ms window, their ops, and one op that crosses
    the window's end."""
    ms = 1e-3
    spans = [("window", 0.0, 10 * ms)]
    modules = [("jit_serve_step(0)", 1 * ms, 4.5 * ms),
               ("jit_serve_step(1)", 6 * ms, 9.5 * ms),
               ("jit_other(3)", 5.0 * ms, 5.2 * ms)]
    ops = [("fusion.1", 1.5 * ms, 3.0 * ms), ("fusion.3", 3.0 * ms, 3.5 * ms),
           ("fusion.1", 6.5 * ms, 8.0 * ms), ("gather.7", 8.0 * ms, 9.0 * ms),
           ("unknown", 9.8 * ms, 10.4 * ms)]
    return ops, modules, spans


def test_scope_seconds_are_counted_as_layer_seconds():
    ops, modules, spans = _trace()
    got = scopes.scope_seconds(ops, spans, scopes.op_scopes(HLO, RMC1_SCOPES))
    assert got == pytest.approx({"sparse/gather": 0.003, "sparse": 0.0005,
                                 "dense/interaction": 0.001, "": 0.0002})
    red = tracing.reduce(ops, modules, spans, {}, step_module="jit_serve_step")
    assert sum(got.values()) == pytest.approx(sum(red.layer_s.values()))


@pytest.mark.parametrize("names, want", [
    (RMC1_SCOPES, {"gather_ms.bulk": 1.5, "pool_ms.bulk": None,
                   "sparse": 0.25, "dense/interaction": 0.5}),
    # a configuration naming only the two halves: the gather is ``sparse``
    (["sparse", "dense"], {"gather_ms.bulk": None, "pool_ms.bulk": None,
                           "sparse": 1.75, "dense/interaction": None}),
])
def test_scope_readers_read_the_configurations_scopes(names, want):
    ops, modules, spans = _trace()
    red = tracing.reduce(ops, modules, spans, {}, step_module=harness.STEP_MODULE)
    red.scope_s = scopes.scope_seconds(ops, spans, scopes.op_scopes(HLO, names))
    run = types.SimpleNamespace(trace=red)
    assert red.launches == 2
    got = {m: harness.reader(m)(run) for m in ("gather_ms.bulk", "pool_ms.bulk")}
    got.update({p: readings.scope_ms(run, p) for p in ("sparse", "dense/interaction")})
    assert got == {k: (v if v is None else pytest.approx(v)) for k, v in want.items()}
    # the scopes' seconds sum to the ops' seconds, as the layers' do
    assert sum(red.scope_s.values()) == pytest.approx(sum(red.layer_s.values()))
    assert readings.scope_ms(types.SimpleNamespace(trace=None), "sparse") is None


def test_idle_splits_into_inside_and_between_step_runs():
    ops, modules, spans = _trace()
    # idle ms: [0, 1.5) 0.5 inside; [3.5, 6.5) 1.0 + 0.5 inside;
    # [9, 9.8) 0.5 inside; the other program's run is no step run
    got = scopes.idle_split(ops, modules, spans, "jit_serve_step")
    assert got == pytest.approx({"in_step": 0.0025, "between_steps": 0.0028})
    busy = tracing.busy_union([(s, e) for _, s, e in ops], 0.0, 0.01)
    assert sum(got.values()) == pytest.approx(0.01 - sum(e - s for s, e in busy))


LEAVES = {"sparse/gather", "sparse/pool", "dense/mlp", "dense/interaction"}


def test_tiny_dlrm_step_on_the_cpu(monkeypatch):
    tiny_program(monkeypatch)
    cell = tiny_cell()
    pcfg = dlrm_family.program_config(cell.cfg)
    dist = traffic.Distributions.from_mix(cell.mix)
    pool = dlrm_family.make_pool(2**31 + 11, D, cell.cfg, dist)
    _, step = harness.build(dlrm_family, pcfg, 2**31 + 11, D, pool)
    text = step.as_text()
    assert all(f"/{leaf}/" in text for leaf in LEAVES)
    # The CPU compiler fuses the whole of G_s into G_d's first op, so only
    # G_d's leaves remain (the v5e compile of RMC1 keeps all four:
    # tests/test_tpu_compile.py); what is left maps to scope paths alone.
    paths = set(scopes.op_scopes(text, cell.cfg["scopes"]).values())
    assert {"dense/mlp", "dense/interaction"} <= paths <= LEAVES | {"", "sparse", "dense"}


def test_probe_reads_launches_by_span_untraced(monkeypatch):
    import probe

    tiny_program(monkeypatch)
    monkeypatch.setattr(harness, "SAMPLE_ITEMS", 256)
    plain = harness.Server, harness.read_trace
    res = probe.probe(tiny_cell(), 2**31 + 13, 0.5, False, time.perf_counter(),
                      log=lambda m: None)
    assert (harness.Server, harness.read_trace) == plain
    assert res["correct"] is True
    got = res["probe"]
    assert got["items_per_s"] == pytest.approx(res["metrics"]["items_per_s"]["value"])
    assert 1 <= len(got["slowest"]) <= 3
    for s in got["slowest"]:
        assert set(s["spans"]) == {"assemble", "device_put", "dispatch", "wait", "readback"}
        assert sum(s["spans"].values()) <= s["seconds"]
    assert "scope_s" not in got


def test_probe_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "probe.py"), "--workload", "rmc1.bulk",
         "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU" in proc.stderr
