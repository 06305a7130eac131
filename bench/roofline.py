"""Operations and bytes of the served model's work, from its shapes.

Counts are what the algorithm needs, not what a program happens to
compute: attention covers the positions a token attends to, and a
paged block's bytes are the least any codec must move (the dense block
read once and written once). ``c`` is a configuration file of
``bench/configs``.
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown chip is
    an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def matmul_params(c: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    MLP, and the LM head (the embedding is a lookup)."""
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def token_flops(c: dict, ctx: int) -> int:
    """FLOPs of one token that attends to ``ctx`` positions (itself
    included): two per multiply-add of the matmuls, plus the scores and
    the weighted sum of values in every layer."""
    attn = 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] * ctx
    return 2 * matmul_params(c) + attn


def prefill_flops(c: dict, prompt_len: int) -> int:
    """FLOPs of a prompt: token ``p`` (0-based) attends to ``p + 1``."""
    n = prompt_len
    attn = 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"]
    return 2 * matmul_params(c) * n + attn * n * (n + 1) // 2


def kv_bytes_per_token(c: dict) -> int:
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def kv_block_bytes(c: dict, block_tokens: int) -> int:
    """Dense bytes of one paged block: keys and values of every layer."""
    return block_tokens * kv_bytes_per_token(c)


def kv_codec_bytes(c: dict, block_tokens: int) -> int:
    """Least bytes of one block's codec round trip: the encode reads the
    dense block once and the decode writes it back once (the compressed
    words, smaller than either, are not counted)."""
    return 2 * kv_block_bytes(c, block_tokens)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline time: the larger of compute and memory bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
