"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick of the roofline and utilization metrics: the work
the live context needs, never the padded spans or buckets the program may
compute over, so a program that does less padded work reads as faster and
not as less work. A multiply-add is two operations.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer that take part in a matmul."""
    D, F = m["hidden_size"], m["intermediate_size"]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    return D * (H + 2 * K) * hd + H * hd * D + 3 * D * F


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context`` keys,
    in every layer."""
    return (4 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * context)


def prefill_flops(m: dict, length: int) -> int:
    """A prompt of ``length`` tokens: every layer on every token, causal
    attention over each token's prefix, and the head on the last token."""
    L = m["num_hidden_layers"]
    return (2 * length * L * layer_matmul_params(m) + 2 * head_params(m)
            + attention_flops(m, length * (length + 1) // 2))


def decode_flops(m: dict, context: int) -> int:
    """One generated token whose attention spans ``context`` keys."""
    return (2 * (m["num_hidden_layers"] * layer_matmul_params(m)
                 + head_params(m)) + attention_flops(m, context))


def request_flops(m: dict, prompt: int, max_new: int) -> int:
    """A served request: its prefill (which yields token 0), then one decode
    step per later token, token i attending the prompt and i tokens."""
    return prefill_flops(m, prompt) + sum(
        decode_flops(m, prompt + i) for i in range(1, max_new))


def kv_bytes_per_token(m: dict, n_bits: int) -> int:
    """Packed K and V of one token in every layer: n_bits codes per element
    and one float32 scale per kv head."""
    K, hd = m["num_key_value_heads"], m["head_dim"]
    return m["num_hidden_layers"] * 2 * K * (hd * n_bits // 8 + 4)


def attention_paged_work(m: dict, *, kv_tokens: int, queries: int,
                         n_bits: int) -> tuple[int, int]:
    """(ops, bytes) of ``attention_paged`` over every layer for one decode
    step: ``queries`` query rows (one per live slot) over ``kv_tokens``
    live cached tokens in all. Bytes are the packed KV read plus the bf16
    queries read and outputs written."""
    H, hd = m["num_attention_heads"], m["head_dim"]
    qo = 2 * queries * H * hd * 2 * m["num_hidden_layers"]
    return (attention_flops(m, kv_tokens),
            kv_tokens * kv_bytes_per_token(m, n_bits) + qo)
