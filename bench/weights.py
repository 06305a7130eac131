"""Seeded random weights, made on the device: the draws every family
builds its layers from.

The benchmark owns its weights. A family (``bench/families``) draws
each layer from ``--seed`` under the reference's own names and lays the
same values out as the serving program expects them, in one jitted
call. The reference (``bench/reference.py``) draws each layer again
after the program's state is freed, so it takes nothing the program
has made.

Every value is an integer drawn from the random bits, times one
constant, rounded once to bf16: no fused multiply-add or other
fusion-dependent rounding can make the two draws differ.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """PRNG key of a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def uniform(key, shape, std: float):
    """bf16 values uniform on +-sqrt(3)*std (standard deviation std)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    k = (bits >> 16).astype(jnp.int32) - 32768
    step = np.float32(math.sqrt(3.0) * std / 32768.0)
    return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)


def norm_scale(key, d: int):
    """bf16 RMSNorm scales in [0.75, 1.25): (192 + k) / 256, exact."""
    bits = jax.random.bits(key, (d,), jnp.uint32)
    k = (bits >> 25).astype(jnp.int32) + 192
    return (k.astype(jnp.float32) * np.float32(1 / 256)).astype(jnp.bfloat16)
