"""Plain reference of a served model, and its lower-precision control.

Written from the published description of the configurations in
``bench/configs``, in float32 at the highest matmul precision: the
embedding lookup, final RMSNorm and untied head here, each layer's
weights and forward from the configuration's family
(``bench/families/<family>.py``). It imports nothing of the serving
program and takes nothing it made: each layer's weights are drawn
again from the seed, one layer at a time, so the whole model is never
resident in float32.

:meth:`Reference.gaps` runs every sampled sequence (prompt + served
tokens) once and returns, for each served token, how far its reference
logit lies below the reference's best at that position. With
``control=True`` it also runs the control: the same forward with every
matrix rounded to block-32 e4m3 (the precision below bf16 that a
QLC-stored weight path would serve), and reads the gap of the token
the control puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import base_key

HI = jax.lax.Precision.HIGHEST


def mm(x, w):
    return jnp.einsum("...i,io->...o", x, w.astype(jnp.float32), precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, c):
    """x [N, S, n, hd] in the source's rotary layout; pos [N, S]."""
    hd = x.shape[-1]
    rot = int(hd * c["partial_rotary_factor"]) // 2 * 2
    inv = 1.0 / (c["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    if c["rope_pairs"] == "halves":
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    else:
        x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
        rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            -1).reshape(x[..., :rot].shape)
    return jnp.concatenate([rotated, x[..., rot:]], -1)


def e4m3_block32(w):
    """Round a matrix to block-32 e4m3 along its input dimension (scale
    = block max / 448) and back to float32."""
    w = w.astype(jnp.float32)
    blocks = w.reshape(w.shape[0] // 32, 32, *w.shape[1:])
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (blocks / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).reshape(w.shape)


def _quantized(w):
    return {k: (v if k.endswith("norm") else e4m3_block32(v)) for k, v in w.items()}


class Reference:
    """Reference forward at a fixed shape: ``rows`` sequences of
    ``seq_len`` positions (shorter ones padded at the end, where
    causality keeps the pad from reaching real positions). ``family``
    is the configuration's family module; each kind of layer it names
    compiles once, the layer's index traced."""

    def __init__(self, family, c: dict, seed: int, rows: int, seq_len: int):
        self.family, self.c, self.rows, self.seq_len = family, c, rows, seq_len
        self.key = base_key(seed)
        self._layer_w = jax.jit(functools.partial(family.layer_weights, c),
                                static_argnums=2)
        self._outer_w = jax.jit(functools.partial(family.outer_weights, c))
        self._layer = jax.jit(functools.partial(family.layer_forward, c),
                              static_argnums=0)
        self._layer_q = jax.jit(
            lambda kind, w, x: family.layer_forward(c, kind, _quantized(w), x),
            static_argnums=0)
        self._embed = jax.jit(self._embed_fn, static_argnums=2)
        self._head = jax.jit(self._head_fn, static_argnums=4)

    def _embed_fn(self, o, tokens, control):
        table = e4m3_block32(o["embed"].T).T if control else o["embed"]
        return jnp.take(table.astype(jnp.float32), tokens, axis=0)

    def _head_fn(self, o, x, xc, targets, control):
        eps = self.c["rms_norm_eps"]
        head = o["lm_head"]
        logits = mm(rms(x, o["final_norm"], eps), head)
        best = jnp.max(logits, -1)
        gap = best - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        lc = mm(rms(xc, o["final_norm"], eps), e4m3_block32(head))
        first = jnp.argmax(lc, -1)
        gap_c = best - jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
        return gap, gap_c

    def gaps(self, seqs, control: bool = False) -> dict:
        """``seqs``: list of ``(prompt, served)`` int arrays, at most
        ``rows``. Returns the widest gap of the served tokens (and of
        the control's first choices) and the number of tokens read."""
        if not 0 < len(seqs) <= self.rows:
            raise ValueError(f"need 1..{self.rows} sequences, got {len(seqs)}")
        tokens = np.zeros((self.rows, self.seq_len), np.int32)
        targets = np.zeros((self.rows, self.seq_len), np.int32)
        mask = np.zeros((self.rows, self.seq_len), bool)
        for i, (prompt, served) in enumerate(seqs):
            prompt, served = np.asarray(prompt), np.asarray(served)
            p, n = prompt.size, served.size
            full = np.concatenate([prompt, served[:-1]])
            if full.size > self.seq_len:
                raise ValueError(f"sequence of {full.size} > {self.seq_len}")
            tokens[i, :full.size] = full
            targets[i, p - 1:p - 1 + n] = served
            mask[i, p - 1:p - 1 + n] = True
        o = self._outer_w(self.key)
        x = self._embed(o, jnp.asarray(tokens), False)
        xc = self._embed(o, jnp.asarray(tokens), True) if control else x
        for layer in range(self.c["num_hidden_layers"]):
            kind = self.family.layer_kind(self.c, layer)
            w = self._layer_w(self.key, layer, kind)
            x = self._layer(kind, w, x)
            if control:
                xc = self._layer_q(kind, w, xc)
        gap, gap_c = self._head(o, x, xc, jnp.asarray(targets), control)
        gap, gap_c = np.asarray(gap)[mask], np.asarray(gap_c)[mask]
        out = {"logit_gap_max": float(gap.max()), "tokens": int(mask.sum())}
        if control:
            out["control_gap_max"] = float(gap_c.max())
        return out
