"""A tiny DLRM cell for the benchmark's tests on the CPU.

The widths are RMC1's; the tables hold 1000 rows each, a bag at most 8
ids, and a launch 64 items, so that a run of the harness takes seconds.
"""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402
from chipbench.families import dlrm as dlrm_family  # noqa: E402

D = 64


END_TO_END = {
    "bulk": [{"name": "items_per_s", "unit": "items/s"}, {"name": "setup_s", "unit": "s"}],
    "open_loop": [{"name": "p99_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
}


def tiny_cell(kind: str = "bulk", rate_qps: float = 40.0) -> harness.Cell:
    """RMC1's configuration at the tiny sizes under ``kind`` traffic (the
    traffic files' distributions, queries of at most two launches)."""
    cfg = harness.load_json(BENCH / "configs" / "dlrm-rmc1.json")
    cfg.update(rows_per_table=1000, pooling=8)
    mix = harness.load_json(BENCH / "traffic" / "bulk.json")
    if kind == "open_loop":
        mix = {"kind": "open_loop", "rate_qps": rate_qps,
               "distributions": dict(mix["distributions"], query_size_max=2 * D)}
    per_layer = [{"name": "search_s", "unit": "s"}, {"name": "host_ms.bulk", "unit": "ms"}]
    return harness.Cell(f"tiny.{kind}", 1, cfg, mix, END_TO_END[kind], per_layer)


def tiny_program(monkeypatch):
    """Point the harness at a program configuration of the tiny sizes and a
    fixed launch size, in place of the paper model and its schedule."""
    from repro.models.embedding import EmbeddingConfig
    from repro.models.recsys_base import RecsysConfig

    def program_config(cfg):
        return RecsysConfig(
            name="tiny", n_dense=cfg["num_dense"], interaction="dot",
            bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
            embedding=EmbeddingConfig(
                vocab_sizes=(cfg["rows_per_table"],) * cfg["num_tables"],
                dim=cfg["embedding_dim"], pooling=(cfg["pooling"],) * cfg["num_tables"],
                row_pad=cfg["weights"]["row_pad"]))

    monkeypatch.setattr(harness, "compile_cache", lambda: None)
    monkeypatch.setattr(dlrm_family, "program_config", program_config)
    monkeypatch.setattr(harness, "schedule",
                        lambda cfg, pcfg: ({"plan": "tiny", "d": D, "m": 1, "o": 1}, 0.0))
