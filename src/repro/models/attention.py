"""Attention layers: GQA with RoPE, flash-style blocked softmax for long
sequences, sliding-window masking (mixtral), and KV-cache decode.

The blocked implementation is the TPU-appropriate formulation: an online
softmax over KV blocks inside a lax.scan keeps activation memory
O(S · block) instead of O(S²) (critical for the prefill_32k cells).
With ``causal_skip`` (perf opt), fully-masked KV blocks are skipped via
a q-block/kv-block scan bound, halving causal attention FLOPs.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.parallel.sharding import logical_constraint

NEG_INF = -2.0 ** 30


def padded_heads(cfg: ModelConfig) -> int:
    h, m = cfg.num_heads, cfg.pad_heads_multiple
    if not m or h % m == 0:
        return h
    return -(-h // m) * m


def init_attention(key, cfg: ModelConfig, dtype):
    d, h, kv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    hp = padded_heads(cfg)
    ks = jax.random.split(key, 4)
    s = 1.0 / d ** 0.5
    so = 1.0 / (h * hd) ** 0.5
    wq = jax.random.normal(ks[0], (d, h, hd), dtype) * s
    wo = jax.random.normal(ks[3], (h, hd, d), dtype) * so
    if hp != h:
        # zero pad slices: padded heads emit exactly 0 through wo and are
        # frozen at use => function identical to the unpadded arch, but
        # the head dim now shards over the model axis.
        wq = jnp.concatenate(
            [wq, jnp.zeros((d, hp - h, hd), dtype)], axis=1)
        wo = jnp.concatenate(
            [wo, jnp.zeros((hp - h, hd, d), dtype)], axis=0)
    return {
        "wq": wq,
        "wk": jax.random.normal(ks[1], (d, kv, hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, kv, hd), dtype) * s,
        "wo": wo,
    }


def _freeze_pad(w, n_real: int, axis: int):
    """stop_gradient on the pad slice so padded heads stay exactly 0."""
    real = jax.lax.slice_in_dim(w, 0, n_real, axis=axis)
    pad = jax.lax.slice_in_dim(w, n_real, w.shape[axis], axis=axis)
    return jnp.concatenate([real, jax.lax.stop_gradient(pad)], axis=axis)


def attention_param_specs():
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def _repeat_kv(x: jnp.ndarray, groups: int) -> jnp.ndarray:
    """[B, S, KV, H] -> [B, S, KV*groups, H] (GQA head expansion)."""
    if groups == 1:
        return x
    b, s, kv, h = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, kv, groups, h)
    ).reshape(b, s, kv * groups, h)


def _expand_kv_padded(x: jnp.ndarray, groups: int, n_real: int,
                      hp: int) -> jnp.ndarray:
    """GQA expansion to hp heads: real head h uses kv[h // groups];
    padded heads (q == 0 anyway) read kv[0]."""
    idx = [min(h_ // groups, x.shape[2] - 1) if h_ < n_real else 0
           for h_ in range(hp)]
    return jnp.take(x, jnp.asarray(idx, dtype=jnp.int32), axis=2)


def _mask(q_pos, k_pos, window: Optional[int]):
    """Causal (+ sliding window) mask: [..., Sq, Sk] bool (True = keep)."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


def dense_attention(q, k, v, q_pos, k_pos, window=None):
    """Reference O(S²) attention. q: [B,Sq,H,D], k/v: [B,Sk,H,D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = _mask(q_pos, k_pos, window)[:, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blocked_attention(q, k, v, q_pos, k_pos, window=None,
                      q_block=512, kv_block=1024, causal_skip=False,
                      score_dtype=jnp.float32):
    """Flash-style attention: scan over q blocks; online softmax over kv
    blocks. Memory O(B·H·q_block·kv_block). Shapes as dense_attention."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0
    nq, nk = sq // q_block, sk // kv_block
    scale = hd ** -0.5

    qs = q.reshape(b, nq, q_block, h, hd).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(b, nq, q_block).transpose(1, 0, 2)
    ks_ = k.reshape(b, nk, kv_block, h, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kv_block, h, hd).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(b, nk, kv_block).transpose(1, 0, 2)

    def q_step(_, q_in):
        qi, qpi = q_in                                  # [B,qb,H,D], [B,qb]

        def kv_step(carry, kv_in):
            acc, m_run, l_run = carry
            kj, vj, kpj = kv_in

            s_ij = (jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                            preferred_element_type=score_dtype)
                    .astype(jnp.float32) * scale)
            # Barrier: the mask depends only on position vectors, and
            # XLA's scan "wide" pass would otherwise precompute and STORE
            # the [B,H,qb,kb] mask for every (iq,ik) pair — gigabytes of
            # pred traffic. Recompute per step instead.
            qpi_b, kpj_b = jax.lax.optimization_barrier((qpi, kpj))
            msk = _mask(qpi_b, kpj_b, window)[:, None]
            s_ij = jnp.where(msk, s_ij, NEG_INF)

            m_new = jnp.maximum(m_run, jnp.max(s_ij, axis=-1))
            p = jnp.exp(s_ij - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = (acc * alpha[..., None]
                   + jnp.einsum("bhqk,bkhd->bhqd", p.astype(vj.dtype),
                                vj).astype(jnp.float32))
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, h, q_block, hd), jnp.float32)
        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        # Inherit varying axes from data (shard_map compatibility).
        zero = (qi.astype(jnp.float32).sum() * 0)
        # Flash-style backward: recompute per-block scores/probabilities
        # instead of storing [nq,nk,B,H,qb,kb] f32 across the whole scan
        # (which costs ~8 GB/layer of residual traffic at 4k).
        kv_step_ck = jax.checkpoint(kv_step)
        (acc, _, l), _ = jax.lax.scan(
            kv_step_ck, (acc0 + zero, m0 + zero, l0 + zero), (ks_, vs, kp))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (qs, qp))       # [nq,B,qb,H,D]
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


class KVCache(NamedTuple):
    k: jnp.ndarray        # [B, S_max, KV, H]
    v: jnp.ndarray        # [B, S_max, KV, H]
    length: jnp.ndarray   # [B] int32 — tokens filled

    @classmethod
    def init(cls, batch: int, max_len: int, kv_heads: int, head_dim: int,
             dtype) -> "KVCache":
        return cls(
            k=jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
            v=jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )


#: seq axis of the K/V arrays counted from the END (leading dims vary:
#: [B, S, KV, H] per layer, [G, B, S, KV, H] stacked over scan groups).
KV_SEQ_AXIS = -3


def kv_block_slice(cache: KVCache, t0: int, t1: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token block ``[t0, t1)`` of a cache — the unit the paged serving
    cache (``repro.serving.kv_cache``) evicts/encodes. Works on a
    per-layer cache or the group-stacked decode-states leaf."""
    sl = (Ellipsis, slice(t0, t1)) + (slice(None),) * (-KV_SEQ_AXIS - 1)
    return cache.k[sl], cache.v[sl]


def kv_block_restore(cache: KVCache, t0: int, t1: int,
                     k: jnp.ndarray, v: jnp.ndarray) -> KVCache:
    """Write block ``[t0, t1)`` back into the cache (decode-on-access
    epilogue of the paged cache) — inverse of :func:`kv_block_slice`."""
    sl = (Ellipsis, slice(t0, t1)) + (slice(None),) * (-KV_SEQ_AXIS - 1)
    return cache._replace(k=cache.k.at[sl].set(k.astype(cache.k.dtype)),
                          v=cache.v.at[sl].set(v.astype(cache.v.dtype)))


def attention_block(params, x, cfg: ModelConfig, positions,
                    cache: Optional[KVCache] = None):
    """Self-attention (training) or attention through a decode cache.

    x: [B, S, D]. With ``cache``, write the S new keys and values at
    ``cache.length`` (the caller keeps ``positions`` starting there)
    and attend causally over the filled prefix: S == 1 is a decode
    step, S > 1 a one-pass prefill. Returns (out [B,S,D],
    new_cache|None).
    """
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    groups = h // kv
    hp = padded_heads(cfg)

    wq, wo = params["wq"], params["wo"]
    if hp != h:
        wq = _freeze_pad(wq, h, 1)
        wo = _freeze_pad(wo, h, 0)
    q = jnp.einsum("bsd,dnh->bsnh", x, wq.astype(x.dtype))
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    q = logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = logical_constraint(v, ("batch", "seq", "kv_heads", "head_dim"))

    if cache is not None:
        # Decode: write this token at position `length`, attend to prefix.
        b, s_in = x.shape[0], x.shape[1]
        idx = cache.length                                   # [B]
        if s_in == 1:
            # Mask-based write: elementwise, so it stays local when the
            # cache's seq dim is sharded (kv_seq -> model/data rules) —
            # a dynamic-update-slice would force a gather under GSPMD.
            s_max = cache.k.shape[1]
            pos_iota = jnp.arange(s_max, dtype=jnp.int32)[None, :, None,
                                                          None]
            writing = pos_iota == idx[:, None, None, None]   # [B,S,1,1]
            k_new = jnp.where(writing, k.astype(cache.k.dtype), cache.k)
            v_new = jnp.where(writing, v.astype(cache.v.dtype), cache.v)
        else:
            # One-pass prefill: the S tokens land at [length, length+S).
            k_new = jax.vmap(
                lambda ck, kn, i: jax.lax.dynamic_update_slice(
                    ck, kn.astype(ck.dtype), (i, 0, 0)))(cache.k, k, idx)
            v_new = jax.vmap(
                lambda cv, vn, i: jax.lax.dynamic_update_slice(
                    cv, vn.astype(cv.dtype), (i, 0, 0)))(cache.v, v, idx)
        new_cache = KVCache(k=k_new, v=v_new, length=idx + s_in)

        q = q[:, :, :h]  # decode path runs unpadded (cache is small)
        # GQA-grouped flash-decode: contract against the cache PER
        # KV-HEAD (no head expansion). The cache's seq dim stays sharded
        # (kv_seq rules); partial scores are shard-local and only the
        # tiny softmax statistics / output reductions cross shards —
        # expanding kv to q-heads instead forces a full f32 cache
        # all-gather (measured: 15 GB/step on chatglm3 decode_32k).
        s_max = k_new.shape[1]
        qg = q.reshape(b, q.shape[1], kv, groups, hd)
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        scale = hd ** -0.5
        scores = (jnp.einsum("bqkgd,bskd->bqkgs", qg, k_new)
                  .astype(jnp.float32) * scale)       # [B,1,KV,G,S]
        valid = (k_pos[None, None, None, None, :]
                 <= positions[:, :, None, None, None])
        if cfg.sliding_window is not None:
            valid &= (k_pos[None, None, None, None, :]
                      > positions[:, :, None, None, None]
                      - cfg.sliding_window)
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bqkgs,bskd->bqkgd",
                         probs.astype(x.dtype), v_new)
        out = out.reshape(b, q.shape[1], h, hd)
    else:
        if hp != h:
            kk = _expand_kv_padded(k, groups, h, hp)
            vv = _expand_kv_padded(v, groups, h, hp)
        else:
            kk = _repeat_kv(k, groups)
            vv = _repeat_kv(v, groups)
        if cfg.attn_impl == "dense":
            out = dense_attention(q, kk, vv, positions, positions,
                                  cfg.sliding_window)
        else:
            out = blocked_attention(
                q, kk, vv, positions, positions, cfg.sliding_window,
                cfg.attn_q_block, cfg.attn_kv_block, cfg.causal_skip,
                score_dtype=jnp.dtype(cfg.attn_score_dtype))
        new_cache = None

    out = logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
    wo_used = wo if out.shape[2] == wo.shape[0] else wo[:out.shape[2]]
    return jnp.einsum("bsnh,nhd->bsd", out,
                      wo_used.astype(out.dtype)), new_cache
