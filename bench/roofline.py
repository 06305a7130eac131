"""Operations and bytes of the served model's work, from its shapes.

Counts are what the algorithm needs, not what a program happens to
compute: attention covers the positions a token attends to, and a
paged block's bytes are the least any codec must move (the dense block
read once and written once). ``c`` is a configuration file of
``bench/configs``; the counts that depend on the architecture
(:func:`matmul_params`, :func:`token_flops`, :func:`prefill_flops`,
:func:`kv_bytes_per_token`) are its family's
(``bench/families/<family>.py``), and this module serves them to the
metrics under names that stay put.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import families


def _family(c: dict):
    return families.module(c["family"])


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown chip is
    an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def matmul_params(c: dict) -> int:
    """Weights a token multiplies through (the embedding is a lookup)."""
    return _family(c).matmul_params(c)


def token_flops(c: dict, ctx: int) -> int:
    """FLOPs of one token that attends to ``ctx`` positions (itself
    included)."""
    return _family(c).token_flops(c, ctx)


def prefill_flops(c: dict, prompt_len: int) -> int:
    """FLOPs of a prompt: token ``p`` (0-based) attends to ``p + 1``."""
    return _family(c).prefill_flops(c, prompt_len)


def kv_bytes_per_token(c: dict) -> int:
    """Dense bytes of the KV cache's entries of one token, every layer."""
    return _family(c).kv_bytes_per_token(c)


def kv_block_bytes(c: dict, block_tokens: int) -> int:
    """Dense bytes of one paged block: its tokens' cache entries, every
    layer."""
    return block_tokens * kv_bytes_per_token(c)


def kv_codec_bytes(c: dict, block_tokens: int) -> int:
    """Least bytes of one block's codec round trip: the encode reads the
    dense block once and the decode writes it back once (the compressed
    words, smaller than either, are not counted)."""
    return 2 * kv_block_bytes(c, block_tokens)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline time: the larger of compute and memory bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
