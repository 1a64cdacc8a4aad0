"""Operations and bytes that the work needs, from its shapes.

These count the algorithm, not the implementation: a prefix sum reads
its input once and writes its output once; a model step does the matrix
products of the tokens it serves. Padding, padded expert capacity,
masked cache slots and recomputation are not counted, so a program that
wastes work reads a lower share of the peak, never a higher one.
"""

from __future__ import annotations

import math
from typing import Iterable


def scan_bytes(shape: Iterable[int], itemsize: int) -> int:
    """HBM bytes of one prefix sum: every element read once and its
    running sum written once."""
    return 2 * math.prod(shape) * itemsize


def moe_matmul_params(m: dict) -> int:
    """Weights one token multiplies by in a top-k MoE decoder, without
    the embedding and the output head: attention projections, router and
    the ``top_k`` experts it is routed to (gated FFN, three matrices)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + 2 * d * m["num_kv_heads"] * hd
    router = d * m["num_experts"]
    experts = m["top_k"] * 3 * d * m["moe_d_ff"]
    return m["num_layers"] * (attn + router + experts)


def attention_flops(m: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` positions,
    all layers and heads."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * keys


def head_flops(m: dict) -> int:
    """One position's logits over the vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A causal prefill of ``prompt_len`` tokens that returns the last
    position's logits."""
    s = prompt_len
    return (2 * moe_matmul_params(m) * s
            + attention_flops(m, 1) * s * (s + 1) // 2 + head_flops(m))


def decode_flops(m: dict, keys: int) -> int:
    """One generated token whose query attends ``keys`` cached positions
    (itself included)."""
    return 2 * moe_matmul_params(m) + attention_flops(m, keys) + head_flops(m)
