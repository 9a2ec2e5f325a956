"""The trace reduction on a small synthetic trace, and the roofline and MFU
arithmetic against work counted by hand for RMC1 and RMC3."""
from __future__ import annotations

import types

import pytest

from chipbench_tiny import BENCH, harness
from chipbench import readings, tracing

# The shape of a compiled step's HLO text as the TPU compiler prints it:
# the source tables, then instructions that point into them.
HLO = """HloModule jit_serve_step, is_scheduled=true

FileNames
1 "/ckpt/run.py"
2 "/ckpt/src/repro/models/dlrm.py"
3 "/ckpt/src/repro/models/embedding.py"
4 "/ckpt/src/repro/models/layers.py"

FunctionNames
1 "serve_step"
2 "apply"

FileLocations
1 {file_name_id=1 function_name_id=1 line=20 end_line=20 column=4 end_column=59}
2 {file_name_id=2 function_name_id=2 line=68 end_line=68 column=11 end_column=63}
3 {file_name_id=3 function_name_id=2 line=148 end_line=148 column=19 end_column=47}
4 {file_name_id=4 function_name_id=2 line=41 end_line=41 column=12 end_column=26}
5 {file_name_id=1 function_name_id=1 line=30 end_line=30 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=2}
4 {file_location_id=4 parent_frame_id=2}
5 {file_location_id=5 parent_frame_id=1}

ENTRY %main.22 (p: f32[10,32]) -> f32[4] {
  %compare_and_fusion = (pred[4,10,8]) fusion(%p), kind=kLoop, metadata={op_name="jit(serve_step)/and" stack_frame_id=3}
  %fusion.1 = f32[320,32]{0,1:T(8,128)} fusion(%p), kind=kCustom, metadata={op_name="jit(serve_step)/gather" stack_frame_id=3}
  %fusion.10 = bf16[4,128] fusion(%p), kind=kOutput, metadata={op_name="jit(serve_step)/dot_general" stack_frame_id=4}
  %fusion.4 = bf16[4,11,11] fusion(%p), kind=kOutput, metadata={op_name="jit(serve_step)/dot_general" stack_frame_id=2}
  %copy.9 = f32[4] copy(%p), metadata={op_name="jit(serve_step)/copy" stack_frame_id=5}
  %copy-start = (f32[4]) copy-start(%p)
  ROOT %tuple.3 = (f32[4]) tuple(%copy.9), metadata={op_name="x" stack_frame_id=9}
}
"""
LAYER_FILES = {"sparse": ["repro/models/embedding.py"],
               "dense": ["repro/models/dlrm.py", "repro/models/layers.py"]}


def test_ops_map_to_layers_by_source_file():
    layers = tracing.op_layers(HLO, LAYER_FILES)
    assert layers["compare_and_fusion"] == "sparse"
    assert layers["fusion.1"] == "sparse"
    assert layers["fusion.10"] == "dense"   # layers.py
    assert layers["fusion.4"] == "dense"    # dlrm.py
    assert layers["copy.9"] == "other"      # only the benchmark's own file
    assert layers["tuple.3"] == "other"     # a frame the tables lack
    assert "copy-start" not in layers       # no metadata: ``other`` later


def test_busy_union_and_gaps():
    busy = tracing.busy_union([(1.0, 2.0), (1.5, 3.0), (4.0, 5.0), (-1.0, 0.5),
                               (9.0, 12.0)], 0.0, 10.0)
    assert busy == [(0.0, 0.5), (1.0, 3.0), (4.0, 5.0), (9.0, 10.0)]
    assert tracing.gaps(busy, 0.0, 10.0) == [(0.5, 1.0), (3.0, 4.0), (5.0, 9.0)]


def test_idle_gaps_are_labelled_by_host_span():
    idle = [(0.5, 1.0), (3.0, 4.0), (5.0, 9.0)]
    spans = [("device_put", 0.4, 0.8), ("dispatch", 0.8, 0.9),
             ("readback", 3.0, 3.5), ("until_due", 5.0, 8.0)]
    got = tracing.idle_by_span(idle, spans)
    assert got == pytest.approx({"device_put": 0.3, "dispatch": 0.1,
                                 "host_other": 0.1 + 0.5 + 1.0,
                                 "readback": 0.5, "until_due": 3.0})


def _synthetic_trace():
    """Two launches in a 10 ms window: ops of each layer, one gap per
    launch spent in ``assemble``, and a module event per launch."""
    ms = 1e-3
    ops, modules, spans = [], [], [("window", 0.0, 10 * ms)]
    for k, t in enumerate((1 * ms, 6 * ms)):
        spans += [("assemble", t - 1 * ms, t - 0.5 * ms),
                  ("dispatch", t - 0.5 * ms, t), ("wait", t, t + 4 * ms)]
        ops += [("fusion.1", t, t + 2 * ms), ("fusion.10", t + 2 * ms, t + 2.5 * ms),
                ("fusion.4", t + 2.5 * ms, t + 3 * ms), ("copy-start", t + 3 * ms, t + 3.5 * ms)]
        modules.append((f"jit_serve_step({k})", t, t + 3.5 * ms))
    modules.append(("jit_other(7)", 9.5 * ms, 9.6 * ms))
    return ops, modules, spans


def test_reduce_a_synthetic_trace():
    ops, modules, spans = _synthetic_trace()
    red = tracing.reduce(ops, modules, spans, tracing.op_layers(HLO, LAYER_FILES),
                         step_module="jit_serve_step")
    assert red.window_s == pytest.approx(0.010)
    assert red.launches == 2
    assert red.busy_s == pytest.approx(0.007)
    assert red.idle_share == pytest.approx(0.3)
    assert red.layer_s == pytest.approx({"sparse": 0.004, "dense": 0.002, "other": 0.001})
    assert red.top_ops[0] == ("sparse:fusion.1", pytest.approx(0.004))
    # idle ms: [0, 1) assemble 0.5, dispatch 0.5; [4.5, 6) wait 0.5,
    # assemble 0.5, dispatch 0.5; [9.5, 10) wait 0.5
    assert red.idle_by_span == pytest.approx(
        {"assemble": 0.001, "dispatch": 0.001, "wait": 0.001})


def _cfg(name):
    return harness.load_json(BENCH / "configs" / f"{name}.json")


# Per item: 2 x (bottom MLP + top MLP + 55 pairwise dots of 32) multiply-adds.
RMC1_FLOPS = 2 * ((13 * 256 + 256 * 128 + 128 * 32)
                  + (87 * 256 + 256 * 64 + 64 * 1) + 55 * 32)        # 161,344
RMC3_FLOPS = 2 * ((13 * 2560 + 2560 * 512 + 512 * 32)
                  + (87 * 512 + 512 * 128 + 128 * 1) + 55 * 32)      # 2,944,704


@pytest.mark.parametrize("name, per_item", [("dlrm-rmc1", RMC1_FLOPS),
                                            ("dlrm-rmc3", RMC3_FLOPS)])
def test_work_counts_by_hand(name, per_item):
    from reference import dlrm

    cfg = _cfg(name)
    assert dlrm.dense_flops_per_item(cfg) == per_item
    w = dlrm.work(cfg, items=1024, valid_lookups=500_000, launches=1)
    assert w["dense_flops"] == 1024 * per_item
    assert w["sparse_bytes"] == 4 * (500_000 * 32 + 1024 * 10 * (cfg["pooling"] + 32))
    assert w["sparse_flops"] == 500_000 * 32


def _run(name, layer_s, window_s=1.0, launches=100, items=102_400,
         lookups=50_000_000):
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = tracing.Reduced(window_s=window_s, busy_s=0.5, launches=launches,
                            layer_s=layer_s, top_ops=[], idle_by_span={})
    cell = types.SimpleNamespace(cfg=_cfg(name))
    return types.SimpleNamespace(cell=cell, trace=trace, peaks=peaks, d=1024,
                                 launches=launches, items=items,
                                 valid_lookups=lookups, window={})


def test_roofline_and_mfu_by_hand_rmc1():
    run = _run("dlrm-rmc1", {"sparse": 0.4, "dense": 0.02})
    sparse_bytes = 4 * (50_000_000 * 32 + 102_400 * 10 * (80 + 32))
    assert readings.sparse_roofline(run) == pytest.approx(
        100 * sparse_bytes / 819e9 / 0.4)
    # RMC1's dense layer is bound by its bytes: weights per launch and the
    # pooled vectors, dense features and logit of every item
    weights = 4 * (13 * 256 + 256 + 256 * 128 + 128 + 128 * 32 + 32
                   + 87 * 256 + 256 + 256 * 64 + 64 + 64 + 1)
    dense_bytes = 100 * weights + 4 * 102_400 * (320 + 13 + 1)
    assert readings.dense_bound(run)[0] == "bytes"
    assert readings.dense_roofline(run) == pytest.approx(
        100 * dense_bytes / 819e9 / 0.02)
    flops = 102_400 * RMC1_FLOPS + 50_000_000 * 32
    assert readings.step_mfu(run) == pytest.approx(100 * flops / 197e12)
    assert readings.sparse_roofline(run) < 100 and readings.step_mfu(run) > 0


def test_roofline_and_mfu_by_hand_rmc3():
    run = _run("dlrm-rmc3", {"sparse": 0.1, "dense": 0.01}, lookups=19_000_000)
    assert readings.dense_bound(run)[0] == "flops"
    assert readings.dense_roofline(run) == pytest.approx(
        100 * 102_400 * RMC3_FLOPS / 197e12 / 0.01)
    assert readings.layer_ms(run, "dense") == pytest.approx(0.1)
    assert readings.device_ms(run) == pytest.approx(5.0)
    assert readings.idle_share(run) == pytest.approx(50.0)


def test_readers_return_nothing_without_a_trace():
    run = _run("dlrm-rmc1", {})
    run.trace = None
    for f in (readings.idle_share, readings.device_ms, readings.step_mfu,
              readings.sparse_roofline, readings.dense_roofline):
        assert f(run) is None
    assert readings.layer_ms(_run("dlrm-rmc1", {"dense": 0.1}), "sparse") is None
