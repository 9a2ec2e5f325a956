"""Compile the main path's kernels and the RMC1 and DIEN serve steps for one
TPU v5e chip that is described, not attached.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned slices, too much fast memory, programs that do not fit).  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the one given this file loads the
TPU library.
"""
import json
import math
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import dien, rmc1
from repro.kernels.embedding_bag.embedding_bag import hot_embedding_bag_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.flash_decode import flash_decode_pallas
from repro.models import din, dlrm
from repro.models.recsys_base import input_specs


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_compiles_for_v5e(one_chip):
    # a hot table that fits VMEM, at the serve_p99 batch and RMC1's pooling
    table = _spec((16384, 32), jnp.float32, one_chip)
    ids = _spec((512, 80), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda t, i: hot_embedding_bag_pallas(t, i, tile_b=128)
    ).lower(table, ids).compile()
    _assert_kernel(compiled)


def test_flash_decode_compiles_for_v5e(one_chip):
    # Qwen2-7B decode_32k: B=8, 28 query heads over 4 KV heads, hd=128
    B, H, KVH, hd, S = 8, 28, 4, 128, 32768
    q = _spec((B, 1, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, KVH, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_decode_pallas(q, k, v, kv_len=S)
    ).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles_for_v5e(one_chip):
    B, T, H, KVH, hd = 1, 4096, 28, 4, 128
    q = _spec((B, T, H, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, T, KVH, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
    ).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


BENCH = pathlib.Path(__file__).parents[1] / "benchmarks" / "chip"


def _scope_paths(hlo: str, config: str) -> set[str]:
    """The scope paths of the entry's ops, by the names the benchmark's
    configuration file lists (``benchmarks/chip/chipbench/scopes.py``)."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from chipbench.scopes import op_scopes

    names = json.loads((BENCH / "configs" / config).read_text())["scopes"]
    return set(op_scopes(hlo, names).values())


def _entry(hlo: str) -> list[str]:
    """The entry computation's instruction lines."""
    body = hlo[hlo.index("\nENTRY "):]
    return body[: body.index("\n}")].splitlines()[1:]


def test_rmc1_serve_step_compiles_for_v5e(one_chip):
    cfg = rmc1(prod=False)
    # 32-wide f32 rows are stored four to a 128-lane line
    assert cfg.embedding.rows_per_line == 4
    params = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    batch = {k: _spec(s.shape, s.dtype, one_chip)
             for k, s in input_specs(cfg, 1024).items()}
    compiled = jax.jit(lambda p, b: dlrm.apply(p, b, cfg)).lower(
        params, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > cfg.embedding.bytes()
    # each layer keeps its named scope through the compiler's fusions, so a
    # device trace can be read by them
    hlo = compiled.as_text()
    paths = _scope_paths(hlo, "dlrm-rmc1.json")
    assert {"sparse/gather", "sparse/pool", "dense/mlp", "dense/interaction"} <= paths

    # the table stays rows-major, and each lookup fetches one whole line
    entry = _entry(hlo)
    table = [ln for ln in entry if "parameter(" in ln and "embedding" in ln]
    assert len(table) == 1 and "= f32[2500096,128]{1,0:T(8,128)} parameter(" in table[0]
    gathers = [ln for ln in hlo.splitlines()
               if " gather(" in ln and "sparse/gather" in ln]
    assert gathers and all("slice_sizes={1,128}" in ln for ln in gathers)
    # no instruction moves the table or the gathered [819200, 128] block:
    # every copy, transpose or relayout is smaller than the block
    block = 1024 * 10 * 80 * 128
    moves = [re.search(r"= \(?\w+\[([\d,]*)\][^ ]* "
                       r"(copy|copy-start|transpose|reshape)\(", ln) for ln in entry]
    moves = [m for m in moves if m]
    assert moves  # the pattern reads this compiler's text
    for m in moves:
        assert math.prod(int(d) for d in m.group(1).split(",") if d) < block, m.string[:200]


def test_dien_serve_step_compiles_for_v5e(one_chip):
    cfg = dien(prod=False)
    # 18-wide f32 rows are padded to 32 lanes and stored four to a line
    emb = cfg.embedding
    assert (emb.row_width, emb.rows_per_line, emb.total_lines) == (32, 4, 527872)
    params = jax.eval_shape(lambda: din.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    batch = {k: _spec(s.shape, s.dtype, one_chip)
             for k, s in input_specs(cfg, 1024).items()}
    assert set(batch) == {"history_ids", "history_cat_ids", "target_id", "target_cat_id",
                          "profile_ids"}
    compiled = jax.jit(lambda p, b: din.apply(p, b, cfg)).lower(
        params, batch).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > cfg.embedding.bytes()
    # the gathers, both recurrences and the attention unit keep their names
    hlo = compiled.as_text()
    paths = _scope_paths(hlo, "dien.json")
    assert {"sparse", "dense/gru", "dense/attention", "dense/augru"} <= paths

    # the table stays rows-major, and each lookup fetches one whole line
    entry = _entry(hlo)
    table = [ln for ln in entry if "parameter(" in ln and "embedding" in ln]
    assert len(table) == 1 and "= f32[527872,128]{1,0:T(8,128)} parameter(" in table[0]
    gathers = [ln for ln in hlo.splitlines() if " gather(" in ln and "sparse" in ln]
    assert gathers and all("slice_sizes={1,128}" in ln for ln in gathers)
    # no instruction copies, transposes or relayouts the table
    moves = [re.search(r"= \(?\w+\[([\d,]*)\][^ ]* "
                       r"(copy|copy-start|transpose|reshape)\(", ln) for ln in entry]
    moves = [m for m in moves if m]
    assert moves  # the pattern reads this compiler's text
    for m in moves:
        assert m.group(1) != "527872,128", m.string[:200]
