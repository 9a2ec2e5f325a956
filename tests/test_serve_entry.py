"""The served path's seams on the CPU: the serving example's ``serve``, the
kernel-mode choice, the compile-cache placement, and ``chip_smoke.py``'s
refusal to run without a TPU."""
import dataclasses
import importlib
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common import compile_cache
from repro.configs import paper_models
from repro.kernels import interpret_mode
from repro.models import dlrm

REPO = pathlib.Path(__file__).resolve().parents[1]


def _tiny_rmc1(prod=True):
    """RMC1's widths (13 dense, 10 tables x 32, pooling 80, both MLPs) with
    1000-row tables, so the test allocates kilobytes, not 1.28 GB."""
    cfg = paper_models.rmc1(prod)
    return dataclasses.replace(cfg, embedding=dataclasses.replace(
        cfg.embedding, vocab_sizes=(1000,) * cfg.embedding.num_features))


def test_serve_answers_queries_on_tiny_rmc1(monkeypatch):
    monkeypatch.setitem(paper_models.PAPER_MODELS, "dlrm-rmc1", _tiny_rmc1)
    monkeypatch.syspath_prepend(str(REPO / "examples"))
    serve_recsys = importlib.import_module("serve_recsys")
    r = serve_recsys.serve("dlrm-rmc1", "T11-v5e", 4, seed=3, qps=1e4)
    assert r.queries == 4 and len(r.latency_ms) == 4
    assert np.all(r.latency_ms > 0)
    assert r.plan.startswith("accel") and r.d in (32, 64, 128, 256, 512, 1024)
    assert r.launches >= r.queries and r.items <= r.launches * r.d
    assert r.last_scores.shape == (r.d,) and np.all(np.isfinite(r.last_scores))
    # the served scores are the model's forward on the launched batch
    want = dlrm.apply(r.params, r.last_batch, _tiny_rmc1(False))
    np.testing.assert_allclose(r.last_scores, np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,explicit,want", [
    ("cpu", None, True),
    ("tpu", None, False),
    ("cpu", False, False),
    ("tpu", True, True),
    ("gpu", True, True),
])
def test_interpret_mode_by_backend(monkeypatch, backend, explicit, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode(explicit) is want


def test_interpret_mode_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode()


def test_compile_cache_left_to_jax_when_env_set(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode != 0
    assert "tpu" in r.stderr.lower()
    assert '"ok": true' not in r.stdout
