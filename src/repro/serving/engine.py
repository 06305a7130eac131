"""Batched serving: prefill + decode with per-layer state caches.

``prefill`` fills the per-layer KV/SSM states from a prompt: in one
forward pass that writes every key and value into the attention caches
where all layers are attention with dense FFNs, else by scanning the
decode step over the tokens (``prefill_mode`` says which);
``generate`` then decodes greedily. The decode step is the function
the decode_* dry-run cells lower.

Compressed-weight serving: ``compress_params_for_serving`` stores the
parameter stack as block-32 e4m3 + QLC words (``repro.comm.weights``)
and ``open_params`` / ``generate_from_wire`` decode them in-graph via
the fused decode→dequantize Pallas kernel — the production path where
FSDP weight gathers move QLC words instead of bf16 and the codec runs
right after the gather. The codec argument may be a per-tensor-type
``CodecRegistry`` (paper §7 multi-LUT): each leaf records its
scheme-id, and ``serving_manifest`` / ``codec_from_manifest``
round-trip the whole recipe (registry included) through JSON so a
serving host reloads it without out-of-band table agreement.

**Deprecation (PR 6)**: the per-call generation functions
(``generate`` / ``generate_paged`` / ``generate_from_wire``) are
superseded by the request-based :class:`repro.serving.scheduler.Engine`
(``submit`` / ``step`` / ``poll``). They remain as thin wrappers
building a one-run engine — token-identical to the scan-based oracle
they replaced (``_generate_scanned``, kept as the reference for tests)
— and emit a ``DeprecationWarning``, the same migration pattern the
PR-4 channel redesign used for the ``qlc_*`` collectives.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, init_decode_states


def _warn_legacy(old: str):
    warnings.warn(
        f"{old} is deprecated; use repro.serving.Engine — submit "
        "GenerationRequests and drive step()/poll()",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class ServeConfig:
    max_seq_len: int
    max_new_tokens: int = 32
    greedy: bool = True


def prefill_mode(cfg: ModelConfig) -> str:
    """How :func:`prefill` runs a prompt under ``cfg``: ``"bulk"`` (one
    forward pass) where every layer is attention with a dense FFN,
    ``"serial"`` (one decode step a token) otherwise. Recurrent layers
    need the scan; MoE layers would route all S tokens at once under
    the capacity factor's drops, which is a different result."""
    kinds = cfg.layer_kinds()
    if all(k == "attention" for k in kinds) and all(
            cfg.ffn_kind(i) != "moe" for i in range(len(kinds))):
        return "bulk"
    return "serial"


def prefill(params, cfg: ModelConfig, tokens: jnp.ndarray,
            states, start_pos=0):
    """Feed a prompt into the decode states, starting at ``start_pos``
    (each row's cache ``length`` must equal it).

    In ``"bulk"`` mode (:func:`prefill_mode`) one forward pass writes
    all S keys and values into the attention caches and masks causally
    per query; the final norm and head run on the last position only.
    Otherwise :func:`prefill_serial` scans the decode step over the
    tokens. tokens: [B, S]. Returns (last_logits [B, V], states).
    """
    if prefill_mode(cfg) == "serial":
        return prefill_serial(params, cfg, tokens, states, start_pos)
    b, s = tokens.shape
    pos = jnp.asarray(start_pos, jnp.int32) + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    logits, states = decode_step(params, cfg, tokens, states, pos)
    return logits[:, 0], states


def prefill_serial(params, cfg: ModelConfig, tokens: jnp.ndarray,
                   states, start_pos=0):
    """Feed a prompt through the decode step token by token — correct
    for every block kind, recurrent and MoE included, and the reference
    the one-pass :func:`prefill` is tested against.

    tokens: [B, S]. Returns (last_logits [B, V], states).
    """
    b, s = tokens.shape

    def body(carry, t):
        st = carry
        tok_t = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        lg, st = decode_step(params, cfg, tok_t, st,
                             jnp.full((b, 1), start_pos, jnp.int32) + t)
        return st, lg[:, 0]

    states, logits_seq = jax.lax.scan(
        body, states, jnp.arange(s, dtype=jnp.int32))
    return logits_seq[-1], states


def _generate_scanned(params, cfg: ModelConfig, prompts: jnp.ndarray,
                      serve_cfg: ServeConfig) -> jnp.ndarray:
    """Scan-based greedy generation — the reference oracle the engine
    and the deprecated wrappers are asserted token-identical against.

    prompts: [B, S] int32. Returns [B, max_new_tokens].
    """
    b, s = prompts.shape
    states = init_decode_states(cfg, b, serve_cfg.max_seq_len)
    logits, states = prefill(params, cfg, prompts, states)

    def body(carry, t):
        tok, st = carry
        lg, st = decode_step(params, cfg, tok, st,
                             jnp.full((b, 1), s, jnp.int32) + t)
        nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)[:, None]
        return (nxt, st), nxt[:, 0]

    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    (_, _), toks = jax.lax.scan(
        body, (first, states),
        jnp.arange(serve_cfg.max_new_tokens - 1, dtype=jnp.int32))
    return jnp.concatenate([first, toks.T], axis=1)


def _engine_generate(params, cfg: ModelConfig, prompts, serve_cfg,
                     **engine_kw) -> jnp.ndarray:
    """One-run engine behind the deprecated batch-call wrappers: one
    request per prompt row, driven to completion."""
    from repro.serving.scheduler import Engine, GenerationRequest
    prompts = np.asarray(prompts)
    b, _ = prompts.shape
    engine_kw.setdefault("max_batch", b)
    eng = Engine(params, cfg, max_seq_len=serve_cfg.max_seq_len,
                 **engine_kw)
    handles = [eng.submit(GenerationRequest(
        prompt=prompts[i], max_new_tokens=serve_cfg.max_new_tokens))
        for i in range(b)]
    eng.run()
    return jnp.asarray(np.stack([eng.poll(h).tokens for h in handles]))


def generate(params, cfg: ModelConfig, prompts: jnp.ndarray,
             serve_cfg: ServeConfig, rng: Optional[jax.Array] = None
             ) -> jnp.ndarray:
    """Greedy generation for a batch of equal-length prompts.

    prompts: [B, S] int32. Returns [B, max_new_tokens].

    .. deprecated:: use :class:`repro.serving.Engine` — this wrapper
       builds a one-run engine (host-driven; not jit-able) and is
       token-identical to the scan oracle it replaced.
    """
    _warn_legacy("generate")
    return _engine_generate(params, cfg, prompts, serve_cfg)


# --------------------------------------------------------------------------
# Compressed-weight serving (QLC wire, fused kernel decode)
# --------------------------------------------------------------------------

def compress_params_for_serving(params, tables, mode: str = "qlc",
                                use_kernels: bool = True,
                                type_key_fn=None):
    """Wire a parameter tree for compressed serving.

    Large (≥64Ki-element-per-group) 2D+ leaves become block-32 e4m3
    symbols packed into QLC slots with exactly-measured capacity (zero
    escapes); everything else stays dense. ``tables`` is a single
    ``CodecTables`` or a per-tensor-type ``CodecRegistry`` (with
    optional ``type_key_fn(leaf_path) -> type name``); each leaf's
    scheme-id lands in the wire codec's manifest. Returns
    ``(wired_params, wire_codec)``; open with :func:`open_params`.
    """
    from repro.comm.weights import compress_groups
    return compress_groups(params, tables, mode=mode,
                           use_kernels=use_kernels,
                           type_key_fn=type_key_fn)


def serving_manifest(wire_codec, *, kv_spec=None, kv_registry=None) -> dict:
    """JSON-able manifest of a wired parameter tree: per-leaf geometry
    + scheme-ids + the codec registry + the channel placement
    (transport / axis / kernel toggle).

    With ``kv_spec`` (a :class:`~repro.serving.kv_cache.KVCacheSpec`),
    the compressed-KV-cache recipe rides along under ``"kv"`` — the
    paging spec plus per-layer ``kv/layer{i}`` scheme-ids, resolved
    against ``kv_registry`` (default: the wire codec's registry, the
    usual one-registry deployment)."""
    from repro.serving.kv_cache import kv_cache_manifest
    m = wire_codec.manifest()
    if kv_spec is not None:
        m["kv"] = kv_cache_manifest(
            kv_spec, kv_registry if kv_registry is not None
            else wire_codec.registry)
    return m


def codec_from_manifest(manifest: dict, use_kernels=None):
    """Rebuild a ``GroupWireCodec`` from :func:`serving_manifest` output
    (tables are re-derived bit-identically from the registry; the
    channel placement rides along). ``use_kernels=None`` keeps the
    manifest's recorded toggle; a bool overrides it. Manifests written
    before the channel placement existed keep this function's historic
    fused-kernel default."""
    from repro.comm.weights import GroupWireCodec
    if use_kernels is None and "channel" not in manifest:
        use_kernels = True          # pre-channel manifests: old default
    return GroupWireCodec.from_manifest(manifest, use_kernels=use_kernels)


def open_params(wired_params, wire_codec, *, channel=None, axis_name=None,
                axis_size=None, transport=None):
    """Decode a QLC-wired parameter tree back to dense arrays in-graph.

    With ``wire_codec.use_kernels`` each leaf is opened by the fused
    decode→dequantize Pallas kernel (one dispatch, symbols stay in
    VMEM); numerics are identical to the pure-JAX open either way.

    Mesh path: with a bound :class:`~repro.comm.channel.Channel` (or
    the loose ``axis_name``/``axis_size``/``transport`` kwargs — the
    channel is the preferred spelling, built once via
    ``wire_codec.channel(axis, axis_size)``), call inside ``shard_map``
    with each compressed leaf sharded along its chunk dim over the
    channel's axis: the wire streams through the transport layer
    instead of a bf16 gather — with the ring transport (default) every
    peer shard's containers decode while the next hop's compressed
    bytes are in flight (``repro.comm.transport`` semantics). Values
    are bit-identical to the unsharded open.
    """
    if channel is not None:
        if channel.axis is None:          # local placement: plain open
            return wire_codec.open_group(wired_params)
        return wire_codec.open_group_sharded(
            wired_params, transport=transport, channel=channel)
    if axis_name is None:
        return wire_codec.open_group(wired_params)
    if axis_size is None:
        raise ValueError("the sharded open needs the static axis_size")
    return wire_codec.open_group_sharded(
        wired_params, axis_name, int(axis_size), transport)


def generate_from_wire(wired_params, wire_codec, cfg: ModelConfig,
                       prompts: jnp.ndarray, serve_cfg: ServeConfig,
                       rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Greedy generation directly from QLC-compressed parameters.

    .. deprecated:: open the wire once (:func:`open_params`) and serve
       the dense tree through :class:`repro.serving.Engine`.
    """
    _warn_legacy("generate_from_wire")
    params = open_params(wired_params, wire_codec)
    return _engine_generate(params, cfg, prompts, serve_cfg)


# --------------------------------------------------------------------------
# Compressed KV-cache serving (block-paged decode states)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _paged_step(cfg: ModelConfig):
    """Jitted one-token greedy decode step, cached per config — the
    engine (sync and async paging alike) and the legacy paged loop run
    this one compiled executable.

    ``(params, tok [B,1], states, pos [B,1]) -> (next tok [B,1] int32,
    pos + 1, states)``: the greedy argmax (first index of the max, as
    ``np.argmax``) runs on device, so the step's output can feed the
    next step without a host round trip. The states are donated: each
    step updates the cache in place, so steps queued ahead on the
    device hold no extra copies."""
    def step(p, tok, st, pos):
        lg, st = decode_step(p, cfg, tok, st, pos)
        nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)[:, None]
        return nxt, pos + 1, st

    return jax.jit(step, donate_argnums=2)


@functools.lru_cache(maxsize=8)
def _prefill_fn(cfg: ModelConfig):
    """Jitted :func:`prefill`, cached per config (the engine's
    admission path; jit re-specializes per prompt length). Its
    executable is ``jit_prefill`` in a device trace, whichever
    :func:`prefill_mode` the config takes."""
    def run(p, tokens, st):
        return prefill(p, cfg, tokens, st)
    run.__name__ = "prefill"
    return jax.jit(run)


@functools.lru_cache(maxsize=8)
def _prefill_from_fn(cfg: ModelConfig):
    """Jitted prefill accepting a start position — the engine's
    *segmented* prefill, which pauses at block boundaries so the SSM
    boundary-state snapshots (``KVCacheSpec.ssm_rebase``) can be
    captured between segments. Each segment starts where the cache's
    ``length`` stands, so feeding a prompt in segments through this
    computes what one whole-prompt :func:`prefill` call does (the same
    mode, the same positions). Its executable is ``jit_prefill_from``."""
    def run(p, tokens, st, start):
        return prefill(p, cfg, tokens, st, start_pos=start)
    run.__name__ = "prefill_from"
    return jax.jit(run)


def _decode_window(cfg: ModelConfig, params, tok, pos, states,
                   window: int):
    """Greedy decode of ``window`` tokens — the async engine's
    admission-window step.

    Each token is one dispatch of :func:`_paged_step`, the sync path's
    own executable, and its output token feeds the next step on device,
    so a window costs one host->device transfer (the seed token +
    positions) and one device->host transfer (the window's tokens),
    independent of ``window`` — the zero-per-token-host-transfer
    contract the transfer-count probe in the tests pins down. One
    ``lax.scan`` over the window would dispatch once, but on a TPU its
    loop body compiles to different roundings than the step, and the
    tokens would drift from the sync path's.

    Returns ``(generated tokens [B, window], states)``.
    """
    step, gen = _paged_step(cfg), []
    for _ in range(window):
        tok, pos, states = step(params, tok, states, pos)
        gen.append(tok)
    return jnp.concatenate(gen, axis=1), states


def generate_paged(params, cfg: ModelConfig, prompts: jnp.ndarray,
                   serve_cfg: ServeConfig, kv_cache=None) -> jnp.ndarray:
    """Greedy generation with a host-driven decode loop paging the
    decode states through a
    :class:`~repro.serving.kv_cache.PagedKVCache`.

    Per-step math is exactly the scan oracle's (same ``decode_step``,
    same greedy argmax); between steps the paged cache evicts every
    completed block — encode to a QLC container, decode back into the
    resident window — so the attended cache content genuinely
    round-trips the compressed wire. With the lossless ``"qlc"`` mode
    the round trip is bit-exact and the output is token-identical to
    ``kv_cache=None``.

    prompts: [B, S] int32. Returns [B, max_new_tokens].

    .. deprecated:: use :class:`repro.serving.Engine` with
       ``kv_spec=``/``pool=`` — per-slot paging through the shared
       digest-addressed block pool. ``kv_cache=None`` already routes
       through the engine; an explicit ``kv_cache`` keeps the legacy
       batch-wide loop (the cache's ``cold``/``stats`` accounting is
       per-batch, which per-slot engine paging deliberately replaces).
    """
    _warn_legacy("generate_paged")
    if kv_cache is None:
        return _engine_generate(params, cfg, prompts, serve_cfg)
    return _paged_loop(params, cfg, prompts, serve_cfg, kv_cache)


def _paged_loop(params, cfg: ModelConfig, prompts: jnp.ndarray,
                serve_cfg: ServeConfig, kv_cache) -> jnp.ndarray:
    """Legacy batch-wide paged decode loop (kept behind the deprecated
    ``generate_paged(kv_cache=...)`` spelling and its tests)."""
    b, s = prompts.shape
    states = init_decode_states(cfg, b, serve_cfg.max_seq_len)
    logits, states = prefill(params, cfg, prompts, states)
    states = kv_cache.note_tokens(states, s)

    step = _paged_step(cfg)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    toks = [tok]
    for t in range(serve_cfg.max_new_tokens - 1):
        pos = jnp.full((b, 1), s + t, jnp.int32)
        tok, _, states = step(params, tok, states, pos)
        states = kv_cache.note_tokens(states, s + t + 1)
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)
