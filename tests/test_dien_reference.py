"""DIEN's served forward (``models/din.py`` with ``use_gru`` and
``item_category``, recurrences in ``models/gru.py``) against the
benchmark's plain reference (``benchmarks/chip/reference/dien.py``), on the
CPU at a small size: tables of a few hundred rows, the published widths
(rows of 18, behaviours and GRUs of 36, attention MLP 80-40, top MLP
200-80), histories of 12 steps, a launch of 16 items.

Tolerance: 1e-5 of ``|program - reference| / (1 + |reference|)``.  Both
sides compute in float32 with full-precision products on the CPU (the
reference at ``highest``), so they differ only in the order of sums: at
most about 2e-7 after 2 x 12 recurrent steps.

Each case runs at the configuration's weights (``scaled``: the program's
draw with the table x 10 and the GRUs' input and state weights x 20 and
x 2, on both sides alike) and at the program's own draw (``drawn``).  At
the drawn weights the recurrences hardly reach the scores (rows of about
1/sqrt(rows), gates of 0.05 x normal); at the scaled ones a dropped gate
or sequence path moves them by more than the benchmark's limit.
"""
import copy
import dataclasses
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import dien
from repro.models import din, gru
from repro.models.embedding import EmbeddingConfig

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from chipbench.families import dien as family  # noqa: E402
from reference import dien as ref  # noqa: E402

TOL = 1e-5
# the benchmark's limit on the chip, which the faults below must miss too
LIMIT = json.loads((BENCH / "configs" / "dien.json").read_text())["check"]["score_gap_limit"]
ROWS = [300, 50, 200, 100]  # item, category, user, context
T, B = 12, 16
SEED = 2**31 + 7


def small_cfg(kind: str = "scaled") -> dict:
    with open(BENCH / "configs" / "dien.json") as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(table_rows=ROWS, history=T)
    if kind == "drawn":
        cfg["weights"].update({k: 1.0 for k in family.SCALES})
    return cfg


def program_cfg():
    emb = EmbeddingConfig(vocab_sizes=tuple(ROWS), dim=18, pooling=(1,) * len(ROWS))
    return dataclasses.replace(dien(prod=False), seq_len=T, embedding=emb)


def program_params(pcfg, kind: str = "drawn"):
    init = family.program(pcfg)[0] if kind == "scaled" else (lambda k: din.init(k, pcfg))
    return jax.jit(init)(jax.random.PRNGKey(SEED % 2**32))


def weights(kind: str):
    """(program, reference) weights from SEED: ``scaled`` by the
    configuration's recipe, ``drawn`` as the program draws them."""
    return program_params(program_cfg(), kind), ref.init(SEED, small_cfg(kind))


def batch_of(lengths) -> dict:
    """Histories of the given lengths (-1 past each), zipf-like ids, each
    item's category its id modulo the category table's rows."""
    rng = np.random.default_rng(3)
    lengths = np.asarray(lengths)
    hist = (rng.integers(0, ROWS[0], (len(lengths), T)) ** 2 // ROWS[0]).astype(np.int32)
    hist[np.arange(T)[None, :] >= lengths[:, None]] = -1
    target = rng.integers(0, ROWS[0], len(lengths)).astype(np.int32)
    prof = np.stack([rng.integers(0, v, len(lengths)) for v in ROWS[2:]], 1).astype(np.int32)
    return {"history_ids": hist, "history_cat_ids": np.where(hist >= 0, hist % ROWS[1], -1),
            "target_id": target, "target_cat_id": target % ROWS[1], "profile_ids": prof}


def gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / (1 + np.abs(want))))


APPLY = jax.jit(din.apply, static_argnums=2)


def scores(params, batch, apply=APPLY):
    return apply(params, batch, program_cfg())


@functools.cache
def _forward(precision):
    return ref.forward_fn(small_cfg(), precision)


def reference(params, batch, precision="highest"):
    return _forward(precision)(params, batch)


def test_reference_weights_are_the_programs():
    """The reference draws the family's scaled program weights bit for bit."""
    params, mine = weights("scaled")
    np.testing.assert_array_equal(mine["table"], params["embedding"]["table"])
    for a, b in zip(mine["attn"] + mine["top"], params["attn_mlp"] + params["top_mlp"]):
        np.testing.assert_array_equal(a["w"], b["w"])
        np.testing.assert_array_equal(a["b"], b["b"])
    for rec in ("gru", "augru"):
        for gate, name in zip(("r", "z", "h"), ref.GATES):
            np.testing.assert_array_equal(mine[rec][name]["w_in"], params[rec][gate]["wx"])
            np.testing.assert_array_equal(mine[rec][name]["w_state"], params[rec][gate]["wh"])
            np.testing.assert_array_equal(mine[rec][name]["b"], params[rec][gate]["b"])


LENGTHS = {
    "length_1": [1] * B,
    "mid_length": [T // 2] * B,
    "full": [T] * B,
    "mixed": list(np.resize([1, 2, 5, T // 2, T - 1, T], B)),
}


@pytest.mark.parametrize("kind", ["drawn", "scaled"])
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_program_agrees_with_reference(case, kind):
    batch = batch_of(LENGTHS[case])
    prog, mine = weights(kind)
    assert gap(scores(prog, batch), reference(mine, batch)) < TOL


@pytest.mark.parametrize("kind", ["drawn", "scaled"])
def test_bfloat16_control_misses_the_tolerance(kind):
    batch = batch_of(LENGTHS["mixed"])
    _, mine = weights(kind)
    want = LIMIT if kind == "scaled" else 10 * TOL
    assert gap(reference(mine, batch, "bfloat16"), reference(mine, batch)) > want


@pytest.mark.parametrize("gate", ["r", "z"])
def test_miscopied_gate_misses_the_tolerance(monkeypatch, gate):
    """The reset or update gate dropped from the program's cell (held at 1)
    is caught, by the tolerance and by the benchmark's limit."""
    batch = batch_of(LENGTHS["mixed"])
    prog, mine = weights("scaled")
    want = reference(mine, batch)
    plain_cell = gru.gru_cell

    def dropped(p, h, x, update_scale=None):
        held = {"wx": jnp.zeros_like(p[gate]["wx"]), "wh": jnp.zeros_like(p[gate]["wh"]),
                "b": jnp.full_like(p[gate]["b"], 1e4)}  # sigmoid(1e4) = 1
        return plain_cell(dict(p, **{gate: held}), h, x, update_scale)

    monkeypatch.setattr(gru, "gru_cell", dropped)
    fresh = jax.jit(lambda p, b, c: din.apply(p, b, c), static_argnums=2)  # traced anew
    assert gap(scores(prog, batch, fresh), want) > max(LIMIT, 10 * TOL)


@pytest.mark.parametrize("fault", ["history_emptied", "augru_zeroed"])
def test_dropped_sequence_path_misses_the_limit(monkeypatch, fault):
    """Scores without the history (every id -1) or with the AUGRU's output
    zeroed miss the benchmark's limit at the configuration's weights."""
    batch = batch_of(LENGTHS["mixed"])
    prog, mine = weights("scaled")
    want = reference(mine, batch)
    if fault == "history_emptied":
        batch = dict(batch, history_ids=np.full_like(batch["history_ids"], -1))
    else:
        plain_run = gru.run_gru
        monkeypatch.setattr(gru, "run_gru", lambda p, xs, att=None: (
            plain_run(p, xs) if att is None else jnp.zeros_like(xs)))
    fresh = jax.jit(lambda p, b, c: din.apply(p, b, c), static_argnums=2)
    assert gap(scores(prog, batch, fresh), want) > LIMIT


def test_augru_keeps_its_state_where_the_attention_is_zero():
    p = gru.init_gru(jax.random.PRNGKey(1), 36, 36)
    h = jax.random.normal(jax.random.PRNGKey(2), (4, 36))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 36))
    np.testing.assert_array_equal(gru.gru_cell(p, h, x, update_scale=jnp.zeros(4)), h)
    # over a sequence: the state after step 3 is kept through a zero tail
    xs = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 36))
    att = jnp.where(jnp.arange(8) < 3, 0.5, 0.0)[None, :].repeat(4, 0)
    hs = gru.run_gru(p, xs, att=att)
    for t in range(3, 8):
        np.testing.assert_array_equal(hs[:, t], hs[:, 2])
    assert not np.array_equal(hs[:, 1], hs[:, 2])


def test_missing_profile_id_gives_the_zero_vector():
    pcfg = program_cfg()
    params = program_params(pcfg)
    ids = jnp.asarray([[3, -1], [-1, 7], [5, 9]], jnp.int32)
    got = np.asarray(din._profile_lookup(params, ids, pcfg)).reshape(3, 2, 18)
    table = np.asarray(params["embedding"]["table"])
    offs = pcfg.embedding.row_offsets
    np.testing.assert_array_equal(got[0, 1], 0)
    np.testing.assert_array_equal(got[1, 0], 0)
    np.testing.assert_array_equal(got[0, 0], table[offs[2] + 3])
    np.testing.assert_array_equal(got[2, 1], table[offs[3] + 9])
