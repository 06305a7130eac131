"""Model assembly: heterogeneous block stacks (dense / MoE / hybrid /
ssm), scan-over-layer-groups, decode states, loss.

Layer stacks are scanned over *groups* of ``cfg.layer_period`` layers so
heterogeneous interleaves (jamba 1 attention : 7 mamba, xlstm sLSTM/
mLSTM alternation) compile to a single rolled loop — essential to keep
the dry-run HLO small at 96 layers.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, moe, ssm
from repro.parallel.sharding import logical_constraint


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _init_block(key, kind: str, cfg: ModelConfig, dtype,
                idx_in_group: int = 0):
    kmix, kffn = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": layers.init_rms_norm(cfg.d_model, dtype)}
    if kind == "attention":
        p["mixer"] = attn.init_attention(kmix, cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = ssm.init_mamba(kmix, cfg, dtype)
    elif kind == "mlstm":
        p["mixer"] = ssm.init_mlstm(kmix, cfg, dtype)
    elif kind == "slstm":
        p["mixer"] = ssm.init_slstm(kmix, cfg, dtype)
    else:
        raise ValueError(kind)
    fk = cfg.ffn_kind(idx_in_group)
    if fk != "none":
        p["norm2"] = layers.init_rms_norm(cfg.d_model, dtype)
        if fk == "moe":
            p["ffn"] = moe.init_moe(kffn, cfg, dtype)
        else:
            p["ffn"] = layers.init_mlp(
                kffn, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def _block_specs(kind: str, cfg: ModelConfig, idx_in_group: int = 0):
    p: Dict[str, Any] = {"norm1": ("embed",)}
    if kind == "attention":
        p["mixer"] = attn.attention_param_specs()
    elif kind == "mamba":
        p["mixer"] = ssm.mamba_param_specs()
    else:
        p["mixer"] = ssm.xlstm_param_specs()
    fk = cfg.ffn_kind(idx_in_group)
    if fk != "none":
        p["norm2"] = ("embed",)
        if fk == "moe":
            p["ffn"] = moe.moe_param_specs(cfg)
        else:
            p["ffn"] = layers.mlp_param_specs(cfg.activation)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    kinds = cfg.layer_kinds()
    n_groups = cfg.num_layers // len(kinds)
    assert n_groups * len(kinds) == cfg.num_layers, (
        f"{cfg.name}: num_layers {cfg.num_layers} not divisible by "
        f"period {len(kinds)}")
    ke, kh, kg = jax.random.split(key, 3)

    def init_group(gkey):
        sub = jax.random.split(gkey, len(kinds))
        return {f"l{i}": _init_block(sub[i], kind, cfg, dtype, i)
                for i, kind in enumerate(kinds)}

    gkeys = jax.random.split(kg, n_groups)
    groups = jax.vmap(init_group)(gkeys)

    params = {
        "embed": layers.init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                       dtype),
        "final_norm": layers.init_rms_norm(cfg.d_model, dtype),
        "groups": groups,
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            kh, (cfg.d_model, cfg.vocab_size), dtype)
            * cfg.d_model ** -0.5)
    return params


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    kinds = cfg.layer_kinds()
    group = {f"l{i}": _block_specs(kind, cfg, i)
             for i, kind in enumerate(kinds)}
    # prepend the scanned "layers" dim to every leaf spec
    group = jax.tree.map(
        lambda spec: ("layers",) + tuple(spec), group,
        is_leaf=lambda s: isinstance(s, tuple) and all(
            x is None or isinstance(x, str) for x in s))
    specs = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "groups": group,
    }
    if not cfg.tie_embeddings:
        specs["head"] = ("embed", "vocab")
    return specs


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _apply_block(p, kind: str, x, positions, cfg: ModelConfig, state):
    h = layers.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attention":
        out, new_state = attn.attention_block(p["mixer"], h, cfg, positions,
                                              cache=state)
    elif kind == "mamba":
        out, new_state = ssm.mamba_block(p["mixer"], h, cfg, state=state)
    elif kind == "mlstm":
        out, new_state = ssm.mlstm_block(p["mixer"], h, cfg, state=state)
    elif kind == "slstm":
        out, new_state = ssm.slstm_block(p["mixer"], h, cfg, state=state)
    else:
        raise ValueError(kind)
    x = x + out
    if "ffn" in p:
        h2 = layers.rms_norm(x, p["norm2"], cfg.norm_eps)
        if "router" in p["ffn"]:
            f = moe.moe_block(p["ffn"], h2, cfg)
        else:
            f = layers.mlp(p["ffn"], h2, cfg.activation)
        x = x + f
    x = logical_constraint(x, ("batch", "seq", "embed"))
    return x, new_state


def _apply_group(params_g, x, positions, cfg: ModelConfig, states_g):
    kinds = cfg.layer_kinds()
    new_states = {}
    for i, kind in enumerate(kinds):
        st = states_g[f"l{i}"] if states_g is not None else None
        x, ns = _apply_block(params_g[f"l{i}"], kind, x, positions, cfg, st)
        new_states[f"l{i}"] = ns
    return x, new_states


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full"


def apply_stack(params, x, positions, cfg: ModelConfig, states=None,
                weight_codec=None):
    """Run all layer groups. states=None (train/prefill-logits) or a
    pytree with leading group dim (decode). ``weight_codec`` (serving):
    group params arrive in compressed wire form and are opened inside
    the scan body, so weight gathers move the wire bytes."""
    groups = params["groups"]

    def open_pg(pg):
        return weight_codec.open_group(pg) if weight_codec is not None \
            else pg

    if states is None:
        def body(carry, pg):
            out, _ = _apply_group(pg, carry, positions, cfg, None)
            return out, None
        body = _maybe_remat(body, cfg)
        if cfg.use_scan:
            x, _ = jax.lax.scan(body, x, groups)
        else:
            n_groups = jax.tree.leaves(groups)[0].shape[0]
            for g in range(n_groups):
                pg = jax.tree.map(lambda a: a[g], groups)
                x, _ = body(x, pg)
        return x, None

    def body_st(carry, inputs):
        pg, sg = inputs
        out, ns = _apply_group(open_pg(pg), carry, positions, cfg, sg)
        return out, ns

    if cfg.use_scan:
        x, new_states = jax.lax.scan(body_st, x, (groups, states))
    else:
        n_groups = jax.tree.leaves(groups)[0].shape[0]
        outs = []
        for g in range(n_groups):
            pg = jax.tree.map(lambda a: a[g], groups)
            sg = jax.tree.map(lambda a: a[g], states)
            x, ns = _apply_group(open_pg(pg), x, positions, cfg, sg)
            outs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, new_states


def _hidden(params, cfg: ModelConfig, tokens: jnp.ndarray,
            prefix_emb: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    dtype = jnp.dtype(cfg.dtype)
    x = layers.embed(params["embed"], tokens).astype(dtype)
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = logical_constraint(x, ("batch", "seq", "embed"))
    x, _ = apply_stack(params, x, positions, cfg, states=None)
    return layers.rms_norm(x, params["final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens: jnp.ndarray,
            prefix_emb: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """tokens: [B, St] -> logits [B, St(+P), V].

    ``prefix_emb`` [B, P, D] (modality stub) is prepended to the token
    embeddings; total sequence = P + St.
    """
    x = _hidden(params, cfg, tokens, prefix_emb, positions)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = layers.unembed(head, x, cfg.tie_embeddings)
    return logical_constraint(logits, ("batch", "seq", "vocab"))


def prefill_logits(params, cfg: ModelConfig, tokens: jnp.ndarray,
                   prefix_emb: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Inference prefill: logits for the LAST position only [B, 1, V]
    (the full [B, S, V] tensor is never materialized)."""
    x = _hidden(params, cfg, tokens, prefix_emb)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return layers.unembed(head, x[:, -1:], cfg.tie_embeddings)


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def init_decode_states(cfg: ModelConfig, batch: int, max_len: int):
    """Fresh per-layer decode states, stacked over groups."""
    kinds = cfg.layer_kinds()
    n_groups = cfg.num_layers // len(kinds)
    dtype = jnp.dtype(cfg.dtype)
    dummy = jnp.zeros((1,), jnp.float32)

    def one(kind):
        if kind == "attention":
            return attn.KVCache.init(batch, max_len, cfg.num_kv_heads,
                                     cfg.resolved_head_dim, dtype)
        if kind == "mamba":
            return ssm.mamba_init_state(dummy, batch, cfg)
        if kind == "mlstm":
            return ssm.mlstm_init_state(dummy, batch, cfg)
        if kind == "slstm":
            return ssm.slstm_init_state(dummy, batch, cfg)
        raise ValueError(kind)

    group = {f"l{i}": one(kind) for i, kind in enumerate(kinds)}
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), group)


def decode_states_specs(cfg: ModelConfig):
    """Logical-axis specs for decode states (for dry-run shardings)."""
    kinds = cfg.layer_kinds()

    def one(kind):
        if kind == "attention":
            return attn.KVCache(
                k=(None, "batch", "kv_seq", "kv_heads", "head_dim"),
                v=(None, "batch", "kv_seq", "kv_heads", "head_dim"),
                length=(None, "batch"))
        if kind == "mamba":
            return ssm.MambaState(ssm=(None, "batch", "mlp", "state"),
                                  conv=(None, "batch", "conv", "mlp"))
        if kind == "mlstm":
            return ssm.MLSTMState(c=(None, "batch", "heads", None, None),
                                  n=(None, "batch", "heads", "head_dim"),
                                  m=(None, "batch", "heads"))
        if kind == "slstm":
            return ssm.SLSTMState(c=(None, "batch", "heads", "head_dim"),
                                  n=(None, "batch", "heads"),
                                  m=(None, "batch", "heads"))
        raise ValueError(kind)

    return {f"l{i}": one(kind) for i, kind in enumerate(kinds)}


def decode_step(params, cfg: ModelConfig, tokens: jnp.ndarray,
                states, positions: jnp.ndarray, weight_codec=None):
    """Decode through the state caches. tokens: [B, S]; positions:
    [B, S] absolute (S == 1 for a decode step; S > 1 writes S tokens
    into the attention caches at once, see ``attention_block``).

    Returns (logits [B, 1, V] of the last position, new_states): the
    final norm and head run on that position alone.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = layers.embed(params["embed"], tokens).astype(dtype)
    x = logical_constraint(x, ("batch", "seq", "embed"))
    x, new_states = apply_stack(params, x, positions, cfg, states=states,
                                weight_codec=weight_codec)
    x = layers.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = layers.unembed(head, x, cfg.tie_embeddings)
    return logits, new_states


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------

def next_token_loss(params, cfg: ModelConfig, tokens: jnp.ndarray,
                    labels: jnp.ndarray,
                    prefix_emb: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross entropy. labels: [B, St] aligned to tokens
    (label t = token t+1); prefix positions carry no loss."""
    logits = forward(params, cfg, tokens, prefix_emb)
    if prefix_emb is not None:
        logits = logits[:, prefix_emb.shape[1]:]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
