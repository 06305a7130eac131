"""Seeded random weights of a dense decoder, made on the device.

The benchmark owns its weights: :func:`layer_weights` draws one layer
from ``--seed`` under the reference's own names, and
:func:`program_params` lays the same values out as the serving program
expects them, in one jitted call. The reference (``bench/reference.py``)
draws each layer again with :func:`layer_weights` after the program's
state is freed, so it takes nothing the program has made.

Every value is an integer drawn from the random bits, times one
constant, rounded once to bf16: no fused multiply-add or other
fusion-dependent rounding can make the two draws differ.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# tensor ids: stable across layers, folded into the layer key
_IDS = {"input_norm": 1, "q": 2, "k": 3, "v": 4, "o": 5,
        "post_norm": 6, "gate": 7, "up": 8, "down": 9}


def base_key(seed: int):
    """PRNG key of a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _uniform(key, shape, std: float):
    """bf16 values uniform on +-sqrt(3)*std (standard deviation std)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    k = (bits >> 16).astype(jnp.int32) - 32768
    step = np.float32(math.sqrt(3.0) * std / 32768.0)
    return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)


def _norm_scale(key, d: int):
    """bf16 RMSNorm scales in [0.75, 1.25): (192 + k) / 256, exact."""
    bits = jax.random.bits(key, (d,), jnp.uint32)
    k = (bits >> 25).astype(jnp.int32) + 192
    return (k.astype(jnp.float32) * np.float32(1 / 256)).astype(jnp.bfloat16)


def shapes(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, f = c["head_dim"], c["intermediate_size"]
    return {"input_norm": (d,), "q": (d, h * hd), "k": (d, kv * hd),
            "v": (d, kv * hd), "o": (h * hd, d), "post_norm": (d,),
            "gate": (d, f), "up": (d, f), "down": (f, d)}


def layer_weights(c: dict, key, layer):
    """One layer's weights under the reference's names: ``[in, out]``
    matrices, q/k columns in the source's own rotary layout."""
    lk = jax.random.fold_in(key, layer + 1)
    d, hd, h = c["hidden_size"], c["head_dim"], c["num_attention_heads"]
    std = {"q": d ** -0.5, "k": d ** -0.5, "v": d ** -0.5,
           "o": (h * hd) ** -0.5, "gate": d ** -0.5, "up": d ** -0.5,
           "down": c["intermediate_size"] ** -0.5}
    out = {}
    for name, shape in shapes(c).items():
        tk = jax.random.fold_in(lk, _IDS[name])
        out[name] = (_norm_scale(tk, shape[0]) if name.endswith("norm")
                     else _uniform(tk, shape, std[name]))
    return out


def outer_weights(c: dict, key):
    """Embedding, final norm and LM head (``[d, vocab]``)."""
    d, v = c["hidden_size"], c["vocab_size"]
    return {"embed": _uniform(jax.random.fold_in(key, 1 << 20), (v, d),
                              d ** -0.5),
            "final_norm": _norm_scale(jax.random.fold_in(key, (1 << 20) + 1), d),
            "lm_head": _uniform(jax.random.fold_in(key, (1 << 20) + 2),
                                (d, v), d ** -0.5)}


def rope_perm(c: dict) -> np.ndarray:
    """Head-dim permutation from the source's rotary layout to the
    program's, which rotates adjacent pairs ``(2j, 2j+1)``. A source
    that rotates halves pairs ``j`` with ``j + rot/2``; the permutation
    moves it to ``2j`` and ``2j+1``. Applied to q and k alike, it leaves
    every attention score as it was."""
    hd = c["head_dim"]
    rot = int(hd * c["partial_rotary_factor"]) // 2 * 2
    perm = np.arange(hd)
    if c["rope_pairs"] == "halves":
        j = np.arange(rot // 2)
        perm[2 * j] = j
        perm[2 * j + 1] = j + rot // 2
    elif c["rope_pairs"] != "adjacent":
        raise ValueError(f"rope_pairs {c['rope_pairs']!r}")
    return perm


def to_program_layer(c: dict, w: dict) -> dict:
    """One reference layer in the program's block layout
    (``repro.models.transformer``: norm1, mixer wq/wk/wv/wo, norm2,
    ffn w_in/w_gate/w_out)."""
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    perm = rope_perm(c)
    return {
        "norm1": w["input_norm"],
        "mixer": {"wq": w["q"].reshape(d, h, hd)[:, :, perm],
                  "wk": w["k"].reshape(d, kv, hd)[:, :, perm],
                  "wv": w["v"].reshape(d, kv, hd),
                  "wo": w["o"].reshape(h, hd, d)},
        "norm2": w["post_norm"],
        "ffn": {"w_in": w["up"], "w_gate": w["gate"], "w_out": w["down"]},
    }


def program_params(c: dict, seed: int):
    """The program's parameter tree, made on the device in one jitted
    call, layer by layer (``lax.map``), so no layer's temporaries
    outlive it."""
    def make(key):
        layers = jax.lax.map(
            lambda i: to_program_layer(c, layer_weights(c, key, i)),
            jnp.arange(c["num_hidden_layers"]))
        o = outer_weights(c, key)
        return {"embed": o["embed"], "final_norm": o["final_norm"],
                "head": o["lm_head"], "groups": {"l0": layers}}
    return jax.jit(make)(base_key(seed))
