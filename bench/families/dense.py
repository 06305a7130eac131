"""Family ``dense``: a decoder of identical layers, each RMSNorm,
rotary attention with grouped KV heads, RMSNorm and a SwiGLU MLP, with
an untied head (phi3-mini-3.8b, chatglm3-6b).

The interface is that of ``bench/families/__init__.py``. Weights are
drawn under the reference's own names (``[in, out]`` matrices, q/k
columns in the source's rotary layout) and laid out in the program's
block layout by :func:`to_program_layer`; the reference's forward is
written from the sources' published description.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, mm, rms, rope
from bench.weights import base_key, norm_scale, uniform

BF16 = 2

#: limit of the widest logit gap at the tiny sizes of :func:`tiny`, set
#: between the readings (CPU, seeds 1, 2, 3, 2**35 + 9, phi3-mini-3.8b
#: and chatglm3-6b): sound runs 0.0094-0.0295, the e4m3 control
#: 0.238-0.504
TINY_LIMIT = 0.1

# --------------------------------------------------------------------------
# The program's configuration
# --------------------------------------------------------------------------

# configuration-file key -> the program's ModelConfig attribute
_SIZES = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "resolved_head_dim",
          "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta", "partial_rotary_factor": "rope_fraction"}


def program_config(c: dict):
    """The program's configuration for a benchmark configuration file,
    checked against every size the file states."""
    from repro.launch.serve import serving_config
    prog = c["program"]
    cfg = serving_config(prog["arch"], reduced=prog.get("reduced", False))
    bad = [f"{k}: file {c[k]} != program {getattr(cfg, a)}"
           for k, a in _SIZES.items() if c[k] != getattr(cfg, a)]
    if (cfg.family, cfg.activation, cfg.param_dtype, cfg.tie_embeddings,
            cfg.sliding_window, cfg.layer_kinds()) != (
            "dense", "swiglu", c["torch_dtype"], False, None, ("attention",)):
        bad.append("not a dense bf16 SwiGLU decoder with an untied head")
    if bad:
        raise ValueError(f"{c['name']}: the program serves another model: {bad}")
    return cfg


def tiny(c: dict) -> dict:
    """``c`` cut to the program's reduced smoke preset
    (``repro.configs.reduced``), same layer kinds."""
    from repro.launch.serve import serving_config
    cfg = serving_config(c["program"]["arch"], reduced=True)
    return dict(c, program={"arch": c["program"]["arch"], "reduced": True},
                hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
                vocab_size=cfg.vocab_size, logit_gap_limit=TINY_LIMIT)


# --------------------------------------------------------------------------
# Weights
# --------------------------------------------------------------------------

# tensor ids: stable across layers, folded into the layer key
_IDS = {"input_norm": 1, "q": 2, "k": 3, "v": 4, "o": 5,
        "post_norm": 6, "gate": 7, "up": 8, "down": 9}

KIND = "decoder"


def layer_kind(c: dict, layer: int) -> str:
    """Every layer is of one kind."""
    return KIND


def shapes(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, f = c["head_dim"], c["intermediate_size"]
    return {"input_norm": (d,), "q": (d, h * hd), "k": (d, kv * hd),
            "v": (d, kv * hd), "o": (h * hd, d), "post_norm": (d,),
            "gate": (d, f), "up": (d, f), "down": (f, d)}


def layer_weights(c: dict, key, layer, kind: str):
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
        out[name] = (norm_scale(tk, shape[0]) if name.endswith("norm")
                     else uniform(tk, shape, std[name]))
    return out


def outer_weights(c: dict, key):
    """Embedding, final norm and LM head (``[d, vocab]``)."""
    d, v = c["hidden_size"], c["vocab_size"]
    return {"embed": uniform(jax.random.fold_in(key, 1 << 20), (v, d),
                             d ** -0.5),
            "final_norm": norm_scale(jax.random.fold_in(key, (1 << 20) + 1), d),
            "lm_head": uniform(jax.random.fold_in(key, (1 << 20) + 2),
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
            lambda i: to_program_layer(c, layer_weights(c, key, i, KIND)),
            jnp.arange(c["num_hidden_layers"]))
        o = outer_weights(c, key)
        return {"embed": o["embed"], "final_norm": o["final_norm"],
                "head": o["lm_head"], "groups": {"l0": layers}}
    return jax.jit(make)(base_key(seed))


# --------------------------------------------------------------------------
# The reference's forward
# --------------------------------------------------------------------------

def layer_forward(c, kind, w, x):
    """One decoder layer over full causal sequences. x: [N, S, d] f32."""
    n, s, _ = x.shape
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (n, s))
    a = rms(x, w["input_norm"], c["rms_norm_eps"])
    q = rope(mm(a, w["q"]).reshape(n, s, h, hd), pos, c)
    k = rope(mm(a, w["k"]).reshape(n, s, kv, hd), pos, c)
    v = mm(a, w["v"]).reshape(n, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)          # query head i reads kv i // (h/kv)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("nhqk,nkhd->nqhd", probs, v, precision=HI)
    x = x + mm(att.reshape(n, s, h * hd), w["o"])
    b = rms(x, w["post_norm"], c["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(b, w["gate"])) * mm(b, w["up"]), w["down"])


# --------------------------------------------------------------------------
# Counts (bench/roofline.py)
# --------------------------------------------------------------------------

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
    """Keys and values of every layer, bf16."""
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * c["head_dim"] * BF16
