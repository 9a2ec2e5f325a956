"""Jitted wrappers whose kernel mode comes from ``interpret_mode``."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.flash_decode import flash_decode_pallas


def flash_attention(q, k, v, *, causal=True, q_offset=0, bq=128, bk=128):
    """Blocked GQA attention: q [B, Tq, H, hd], k/v [B, Tk, KVH, hd]."""
    return flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset, bq=bq, bk=bk,
        interpret=interpret_mode(),
    )


def flash_decode(q, k, v, *, kv_len, kv_offset=0, bk=512):
    """Split-KV decode: q [B, 1, H, hd] against cache k/v [B, S, KVH, hd].

    kv_offset: global position of k/v row 0 (non-zero for a shard of a
    sequence-sharded cache); kv_len masks against global position.
    """
    return flash_decode_pallas(q, k, v, kv_len=kv_len, kv_offset=kv_offset,
                               bk=bk, interpret=interpret_mode())
