"""The benchmark harness: its files, its refusal without a chip, and a whole
run on the CPU at a tiny size, sound and with the timed path broken."""
from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import time

import pytest

from chipbench_tiny import BENCH, ROOT, dlrm_family, harness, tiny_cell, tiny_program

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_its_file(spec):
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (BENCH / "reference" / f"{cfg['family']}.py").exists()
    for w in spec["workloads"]:
        cell = harness.resolve(w["name"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
    assert "TPU v5 lite" in json.loads((BENCH / "peaks.json").read_text())


def test_names_and_units_use_allowed_characters(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w[k] for w in spec["workloads"] for k in ("config", "traffic")]
    metrics = spec["end_to_end"] + spec["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in spec[kind]]
        assert len(seen) == len(set(seen))
    per_layer = {m["name"] for m in spec["per_layer"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])
    assert not per_layer & e2e


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "rmc1.bulk",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU" in proc.stderr


def _run(monkeypatch, kind="bulk", fault=None, seed=2**31 + 5, trace=False):
    tiny_program(monkeypatch)
    monkeypatch.setattr(harness, "SAMPLE_ITEMS", 256)
    cell = tiny_cell(kind)
    return harness.run(cell, seed, 0.5, trace, time.perf_counter(),
                       fault=fault, log=lambda m: None)


@pytest.mark.parametrize("kind", ["bulk", "open_loop"])
def test_sound_run_is_correct(monkeypatch, kind):
    res = _run(monkeypatch, kind)
    assert res["correct"] is True, res["compared"]
    assert list(res)[-1] == "compared"
    assert res["compared"]["score_gap"]["value"] < 1e-5
    want = {"bulk": "items_per_s", "open_loop": "p99_ms"}[kind]
    assert set(res["metrics"]) == {want, "setup_s"}
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "sparse_dropped", "control_bfloat16"])
@pytest.mark.parametrize("kind", ["bulk", "open_loop"])
def test_broken_timed_path_is_not_correct(monkeypatch, kind, fault):
    res = _run(monkeypatch, kind, fault)
    assert res["correct"] is False, (fault, res["compared"])


@pytest.mark.parametrize("row_pad, rows", [(512, 10_000_384), (2048, 10_000_384),
                                           (1000, 10_000_000), (4096, 10_002_432)])
def test_program_config_checks_the_padded_rows(row_pad, rows):
    """RMC1's program pads its packed table to 10,000,384 rows (lines of
    four, to 512 lines); a file whose reference pads to another count
    would draw other weights, and is refused with both counts."""
    cfg = copy.deepcopy(harness.load_json(BENCH / "configs" / "dlrm-rmc1.json"))
    cfg["weights"]["row_pad"] = row_pad
    if rows == 10_000_384:
        assert dlrm_family.program_config(cfg).embedding.total_rows == rows
    else:
        with pytest.raises(SystemExit, match=f"10000384 rows.* to {rows}"):
            dlrm_family.program_config(cfg)
