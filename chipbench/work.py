"""Operations and bytes that the served model's work requires, from
shapes and live lengths alone.

Roofline shares count the same work whatever implements it: the keys
and values of live tokens, the rows whose logits are needed, never the
pages or padding a kernel happens to sweep.  FLOPs count a
multiply-add as two.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2  # bfloat16 cache
ACT_BYTES = 2  # bfloat16 activations


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add it with its source")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["hidden_size"] // c["num_attention_heads"],
                   c["intermediate_size"], c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        M, H, K, D, F = (self.d_model, self.heads, self.kv_heads,
                         self.head_dim, self.d_ff)
        return 2 * M * H * D + 2 * M * K * D + 3 * M * F


def paged_attention(s: Shape, contexts: Sequence[int]) -> tuple:
    """One layer's decode attention: each active slot's one query over
    its ``context`` live keys.  Returns (flops, bytes)."""
    n = sum(contexts)
    flops = 4 * s.heads * s.head_dim * n
    kv = 2 * n * s.kv_heads * s.head_dim * KV_BYTES
    qo = 2 * len(contexts) * s.heads * s.head_dim * ACT_BYTES
    return flops, kv + qo


def flash_prefill(s: Shape, start: int, end: int) -> tuple:
    """One layer's causal attention of the chunk's valid queries
    ``[start, end)`` over keys ``[0, i]``.  Returns (flops, bytes)."""
    keys = (start + 1 + end) * (end - start) // 2  # sum of (i + 1)
    flops = 4 * s.heads * s.head_dim * keys
    kv = 2 * end * s.kv_heads * s.head_dim * KV_BYTES
    qo = 2 * (end - start) * s.heads * s.head_dim * ACT_BYTES
    return flops, kv + qo


def step_flops(s: Shape, contexts: Sequence[int], chunk=None) -> int:
    """Model FLOPs of one fused step: a token for every active slot
    (``contexts`` = keys each attends) with its logits, and the chunk's
    valid prompt tokens ``(start, end)`` with the logits of one row."""
    per_tok = 2 * s.layers * s.layer_matmul_params
    head = 2 * s.d_model * s.vocab
    f = len(contexts) * (per_tok + head)
    f += s.layers * paged_attention(s, contexts)[0]
    if chunk is not None:
        start, end = chunk
        f += (end - start) * per_tok + head
        f += s.layers * flash_prefill(s, start, end)[0]
    return f


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
