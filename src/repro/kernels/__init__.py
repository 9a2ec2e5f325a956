"""Pallas TPU kernels for the perf-critical compute layers.

- embedding_bag: fused SparseLengthsSum over a VMEM-resident hot table —
  the TPU-native adaptation of the paper's hot-embedding partition (the
  NMP Gather-Reduce insight mapped to the HBM->VMEM hierarchy).
- flash_attention: blocked causal GQA attention (prefill) + split-KV decode
  for the LM serving cells.

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper whose kernel mode comes from :func:`interpret_mode`) and ref.py
(pure-jnp oracle); tests sweep shapes/dtypes against the oracle.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    An explicit ``interpret`` wins.  Otherwise the default backend decides:
    interpret on ``"cpu"``, compile on ``"tpu"``, and raise on any other
    backend — a kernel never falls back to the interpreter silently on a
    host where JAX found some other accelerator.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here compile for TPU or interpret on CPU; the "
        f"default JAX backend is {backend!r}. Pass interpret= explicitly.")
