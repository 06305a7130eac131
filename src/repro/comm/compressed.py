"""QLC-compressed collectives (the paper's motivating application, §1).

Built on shard_map + jax.lax collectives. The wire format is shape-static
(XLA requirement): each 1024-symbol chunk gets a fixed QLC slot sized by
the planner, a 1-byte escape flag, and escaped chunks ride in a small
fixed overflow pool. If the pool itself overflows (probability bounded
below the planner's target; adversarial data only), the payload is
flagged not-ok and the caller retries the step uncompressed — the
trainer implements that retry. Lossless semantics never depend on
statistics.

Collectives:
  qlc_all_gather      — AG of e4m3-quantized, QLC-coded shards.
  qlc_reduce_scatter  — RS as quantize-encode + all_to_all + decode-sum.
  qlc_psum            — RS followed by AG (both compressed).
  qlc_all_to_all      — compressed expert/MoE dispatch.

Each has an uncompressed-e4m3 twin (cfg.enabled=False → raw codes on the
wire) and a bf16 reference; the coding step is bit-exact lossless, so
compressed and raw-e4m3 paths produce IDENTICAL numerics (tested).

Codec arguments: every entry point accepts either the legacy
``(CodecTables, CommConfig)`` pair or a
:class:`~repro.core.registry.CodecEntry` from a per-tensor-type
registry (``resolve_codec`` is the shim); the entry's calibrated plan
supplies the wire config. For payloads that must decode WITHOUT this
out-of-band config (checkpoints, serving manifests, offline exchange),
``repro.comm.container`` frames them with a self-describing header
(scheme-id + chunk geometry + capacity + pool + scale layout).

**Deprecation**: the loose-kwarg functional API here (``qlc_*``,
``compress_values``, ``decompress_values``, ...) is superseded by
:class:`repro.comm.channel.Channel`, which binds codec + transport +
mesh axis once and exposes the same surface as methods. The functions
remain as thin wrappers building a channel per call — bit-identical
outputs — and emit a ``DeprecationWarning``.

With ``cfg.use_kernels=True`` the local quantize→encode and
decode→dequantize stages each run as one fused Pallas dispatch
(``repro.kernels.ops``) instead of separate XLA ops — same numerics.
(On this path the uint8 symbols ARE still written once to HBM, because
the escape pool needs them; the fusion saves the separate quantize and
encode dispatches and their re-reads. Callers without an escape pool —
the weight wire, serving, checkpoints — get the full
symbols-stay-in-VMEM benefit.) Note: ``pallas_call`` has no shard_map
replication rule, so kernels run inside ``repro.parallel.sharding.
shard_map``, which turns replication checking off.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.lut import CodecTables
from repro.comm.planner import CommPlan
from repro.quant import e4m3


def _warn_legacy(old: str, new: str):
    warnings.warn(
        f"{old} is deprecated; bind the codec once with "
        f"repro.comm.channel.Channel and call {new}",
        DeprecationWarning, stacklevel=3)


def _legacy_channel(tables, cfg, *, transport=None, axis_name=None,
                    axis_size=None):
    """One-shot Channel for a deprecated functional call."""
    from repro.comm.channel import Channel, ChannelSpec
    tables, cfg = resolve_codec(tables, cfg)
    return Channel(ChannelSpec(codec=tables, cfg=cfg, transport=transport,
                               axis=axis_name, axis_size=axis_size))


def resolve_codec(codec_like, cfg: Optional["CommConfig"] = None,
                  **cfg_overrides):
    """Normalize a codec argument to ``(tables, cfg)``.

    Accepts the legacy ``(CodecTables, CommConfig)`` pair or a registry
    :class:`~repro.core.registry.CodecEntry`, whose plan supplies the
    wire config when ``cfg`` is omitted (overrides, e.g.
    ``use_kernels=True``, apply on top). This is the API-migration
    shim: every collective and (de)compression entry point routes
    through it.
    """
    from repro.core.registry import CodecEntry
    if isinstance(codec_like, CodecEntry):
        tables = codec_like.tables
        if cfg is None:
            cfg = codec_like.config(**cfg_overrides)
        return tables, cfg
    if cfg is None:
        raise TypeError(
            "a bare CodecTables needs an explicit CommConfig; pass a "
            "registry CodecEntry to derive it from the calibrated plan")
    return codec_like, cfg


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Static configuration of the compressed-collective wire format."""
    enabled: bool = True          # False => raw e4m3 codes on the wire
    chunk_symbols: int = 1024
    capacity_words: int = 240     # 7.5 bits/symbol default
    pool_slots_per_1k: int = 8
    scale_dtype: str = "bfloat16"
    # Fused Pallas kernels inside the graph: quantize+encode and
    # decode+dequantize each run as one dispatch (repro.kernels.ops).
    # Bit-exact vs the pure-JAX path; compiled on TPU, interpret on CPU.
    use_kernels: bool = False

    @classmethod
    def from_plan(cls, plan: CommPlan, **kw) -> "CommConfig":
        base = dict(chunk_symbols=plan.chunk_symbols,
                    capacity_words=plan.capacity_words,
                    pool_slots_per_1k=plan.pool_slots_per_1k)
        base.update(kw)          # explicit overrides win over the plan
        return cls(**base)

    def pool_slots(self, n_chunks: int) -> int:
        return max(1, math.ceil(n_chunks * self.pool_slots_per_1k / 1024))

    def raw_words(self) -> int:
        return self.chunk_symbols // 4


class WirePayload(NamedTuple):
    """Static-shape compressed payload for one (src -> dst) transfer."""
    words: jnp.ndarray       # u32 [..., n_chunks, capacity_words]
    flags: jnp.ndarray       # u8  [..., n_chunks] 1 = escaped-to-pool
    pool: jnp.ndarray        # u32 [..., pool_slots, K/4] raw escaped chunks
    pool_count: jnp.ndarray  # i32 [..., 1] number of escapes


def wire_bytes(payload: WirePayload, scales: Optional[jnp.ndarray] = None
               ) -> int:
    """Static wire footprint in bytes (for accounting/benchmarks)."""
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in payload)
    if scales is not None:
        total += int(np.prod(scales.shape)) * scales.dtype.itemsize
    return total


# --------------------------------------------------------------------------
# Payload compress / decompress (local, shape-static, jit-friendly)
# --------------------------------------------------------------------------

def _encode(chunks: jnp.ndarray, tables: CodecTables, cfg: CommConfig):
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        flat = chunks.reshape(-1, cfg.chunk_symbols)
        words, nbits = kops.encode(flat, tables, cfg.capacity_words)
        lead = chunks.shape[:-1]
        return (words.reshape(lead + (cfg.capacity_words,)),
                nbits.reshape(lead))
    return codec.encode_chunks(chunks, tables, cfg.capacity_words)


def _decode(words: jnp.ndarray, tables: CodecTables, cfg: CommConfig):
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        flat = words.reshape(-1, cfg.capacity_words)
        out = kops.decode(flat, tables, cfg.chunk_symbols)
        return out.reshape(words.shape[:-1] + (cfg.chunk_symbols,))
    return codec.decode_chunks(words, tables, cfg.chunk_symbols)


def words_of_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """u8 [..., 4m] -> u32 [..., m], little-endian (``ndarray.view``).

    Shifts of strided slices rather than a bitcast of a ``[..., 4]``
    view: on a TPU that view's minor dimension of 4 pads to 128 lanes
    (32x the bytes) wherever XLA materializes it.
    """
    b = b.astype(jnp.uint32)
    return (b[..., 0::4] | (b[..., 1::4] << 8) | (b[..., 2::4] << 16)
            | (b[..., 3::4] << 24))


def bytes_of_words(w: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`words_of_bytes`: u32 [..., m] -> u8 [..., 4m]."""
    shift = (jnp.arange(4 * w.shape[-1], dtype=jnp.uint32) % 4) * 8
    return ((jnp.repeat(w, 4, axis=-1) >> shift) & 0xFF).astype(jnp.uint8)


def _raw_payload(chunks: jnp.ndarray) -> WirePayload:
    """Raw e4m3 wire: u8 -> u32 words, no escapes."""
    *lead, n_chunks, k = chunks.shape
    raw = words_of_bytes(chunks)
    return WirePayload(
        words=raw,
        flags=jnp.zeros((*lead, n_chunks), dtype=jnp.uint8),
        pool=jnp.zeros((*lead, 1, k // 4), dtype=jnp.uint32),
        pool_count=jnp.zeros((*lead, 1), dtype=jnp.int32),
    )


# --- escape-pool machinery (shared by wire assembly and both decode
# --- paths; the slot/gather invariants live ONLY here) --------------------

def _escape_slots(escape: jnp.ndarray, pool_slots: int):
    """Per-chunk pool slot assignment from escape flags.

    Returns ``(esc_idx, slot)``: running escape index, and the scatter
    slot (``pool_slots`` — i.e. dropped — for non-escaped and
    pool-overflowing chunks).
    """
    esc_i = escape.astype(jnp.int32)
    esc_idx = jnp.cumsum(esc_i, axis=-1) - esc_i
    slot = jnp.where(escape.astype(bool), esc_idx, pool_slots)
    return esc_idx, slot


def _scatter_pool_rows(rows: jnp.ndarray, slot: jnp.ndarray,
                       pool_slots: int) -> jnp.ndarray:
    """[..., n_chunks, W] rows -> [..., pool_slots, W] (drop slot==pool_slots)."""
    *lead, n_chunks, w = rows.shape

    def one(z, s_, v_):
        return z.at[s_].set(v_, mode="drop")

    zeros = jnp.zeros((*lead, pool_slots, w), rows.dtype)
    if lead:
        out = jax.vmap(one)(zeros.reshape(-1, pool_slots, w),
                            slot.reshape(-1, n_chunks),
                            rows.reshape(-1, n_chunks, w))
        return out.reshape(*lead, pool_slots, w)
    return one(zeros, slot, rows)


def _gather_pool_rows(pool: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """[..., pool_slots, W] pool + [..., n_chunks] idx -> [..., n_chunks, W]."""
    *lead, pool_slots, w = pool.shape
    n_chunks = idx.shape[-1]

    def one(pv, iv):
        return jnp.take(pv, iv, axis=0)

    if lead:
        out = jax.vmap(one)(pool.reshape(-1, pool_slots, w),
                            idx.reshape(-1, n_chunks))
        return out.reshape(*lead, n_chunks, w)
    return one(pool, idx)


def _assemble_payload(chunks: jnp.ndarray, words: jnp.ndarray,
                      nbits: jnp.ndarray, cfg: CommConfig) -> WirePayload:
    """Build the escape-flag/pool wire format around encoded slots."""
    *lead, n_chunks, k = chunks.shape
    escape = nbits > jnp.uint32(cfg.capacity_words * 32)
    pool_slots = cfg.pool_slots(n_chunks)

    raw = words_of_bytes(chunks)

    # Escaped chunks scatter their raw form into the pool; non-escaped
    # and pool-overflowing chunks are dropped.
    _, slot = _escape_slots(escape, pool_slots)
    pool = _scatter_pool_rows(raw, slot, pool_slots)

    pool_count = jnp.sum(escape.astype(jnp.int32), axis=-1, keepdims=True)
    return WirePayload(words=words, flags=escape.astype(jnp.uint8),
                       pool=pool, pool_count=pool_count)


def _compress_codes(codes: jnp.ndarray, tables: CodecTables,
                    cfg: CommConfig) -> WirePayload:
    """Resolved-argument impl of :func:`compress_codes` (the
    non-deprecated path — ``Channel.compress_codes`` and the transport
    layer land here)."""
    k = cfg.chunk_symbols
    *lead, m = codes.shape
    assert m % k == 0, (m, k)
    n_chunks = m // k
    chunks = codes.reshape(*lead, n_chunks, k)

    if not cfg.enabled:
        return _raw_payload(chunks)

    words, nbits = _encode(chunks, tables, cfg)
    return _assemble_payload(chunks, words, nbits, cfg)


def compress_codes(codes: jnp.ndarray, tables, cfg: CommConfig = None
                   ) -> WirePayload:
    """uint8 [..., M] (M % chunk_symbols == 0) -> WirePayload.

    ``tables`` is a ``CodecTables`` (with explicit ``cfg``) or a
    registry ``CodecEntry`` (cfg defaults to its calibrated plan).

    .. deprecated:: use ``Channel.compress_codes``.
    """
    _warn_legacy("compress_codes", "Channel.compress_codes")
    return _legacy_channel(tables, cfg).compress_codes(codes)


def _gather_pool_raw(payload: WirePayload, cfg: CommConfig) -> jnp.ndarray:
    """Gather each chunk's escape-pool raw form -> u8 [..., n_chunks, K].

    Rows whose chunk did not escape hold arbitrary pool data; callers
    select with the escape flags.
    """
    k = cfg.chunk_symbols
    *lead, n_chunks, _ = payload.words.shape
    pool_slots = payload.pool.shape[-2]
    esc_idx, _ = _escape_slots(payload.flags, pool_slots)
    raw_words = _gather_pool_rows(
        payload.pool, jnp.minimum(esc_idx, pool_slots - 1))
    return bytes_of_words(raw_words).reshape(*lead, n_chunks, k)


def decompress_codes(payload: WirePayload, tables,
                     cfg: CommConfig = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """WirePayload -> (uint8 codes [..., M], ok bool[...]).

    .. deprecated:: use ``Channel.decompress_codes``.
    """
    _warn_legacy("decompress_codes", "Channel.decompress_codes")
    if tables is not None or cfg is None:
        tables, cfg = resolve_codec(tables, cfg)
    return _decompress_codes(payload, tables, cfg)


def _decompress_codes(payload: WirePayload, tables: Optional[CodecTables],
                      cfg: CommConfig, *, decode_fn=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolved-argument impl of :func:`decompress_codes`. ``tables``
    may be ``None`` only for a raw (``cfg.enabled=False``) wire.
    ``decode_fn(words, tables, cfg)`` overrides the slot decode — the
    async KV paging path routes it through the DMA prefetch kernel
    (``kernels.ops.decode_block_async``) while reusing this escape
    merge unchanged."""
    k = cfg.chunk_symbols
    *lead, n_chunks, _ = payload.words.shape

    if not cfg.enabled:
        codes_out = bytes_of_words(payload.words).reshape(
            *lead, n_chunks * k)
        ok = jnp.ones(tuple(lead), dtype=bool) if lead else jnp.bool_(True)
        return codes_out, ok

    dec = (_decode if decode_fn is None else decode_fn)(
        payload.words, tables, cfg)                    # [..., n_chunks, K]

    escape = payload.flags.astype(bool)
    raw = _gather_pool_raw(payload, cfg)
    pool_slots = payload.pool.shape[-2]

    out = jnp.where(escape[..., None], raw, dec)
    ok = (payload.pool_count[..., 0] <= pool_slots)
    return out.reshape(*lead, n_chunks * k), ok


# --------------------------------------------------------------------------
# Quantization plumbing
# --------------------------------------------------------------------------

def _quantize(x: jnp.ndarray, cfg: CommConfig):
    """float [..., M] -> (codes u8 [..., M], scales scale_dtype [..., M/32])."""
    codes, scales = e4m3.quantize_block32(x.astype(jnp.float32))
    return codes, scales.astype(cfg.scale_dtype)


def _dequantize(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    return e4m3.dequantize_block32(codes, scales.astype(jnp.float32))


# --------------------------------------------------------------------------
# Fused value <-> wire transforms (the collectives' local hot path)
# --------------------------------------------------------------------------

def compress_values(x: jnp.ndarray, tables, cfg: CommConfig = None
                    ) -> Tuple[WirePayload, jnp.ndarray]:
    """float [..., M] (M % chunk_symbols == 0) -> (WirePayload, scales).

    ``tables`` may be a registry ``CodecEntry`` (cfg optional, derived
    from its plan). For a self-describing framing of the result see
    ``repro.comm.container`` — the container header carries the wire
    geometry + scheme-id so the payload decodes without this cfg.

    .. deprecated:: use ``Channel.compress``.
    """
    _warn_legacy("compress_values", "Channel.compress")
    return _legacy_channel(tables, cfg).compress(x)


def _compress_values(x: jnp.ndarray, tables: CodecTables, cfg: CommConfig,
                     *, emit_hist: bool = False):
    """Resolved-argument impl of :func:`compress_values`.

    With ``cfg.use_kernels`` the e4m3 quantization and QLC encode run as
    ONE fused Pallas dispatch (the symbols are emitted once, for the
    escape pool, instead of being written by quantize and re-read by
    encode); otherwise the pure-JAX quantize -> encode pipeline runs.
    Both paths are bit-exact identical: the fused kernel's quantizer is
    tested bit-equal to ``e4m3.quantize_block32`` and its packer to
    ``codec.encode_chunks``.

    ``emit_hist=True`` appends the 256-bin symbol histogram (i32[256],
    summed over ALL lead dims) to the return: on the kernel path it
    rides the fused encode pass for free (the symbols are already in
    registers); the pure path pays one ``bincount``. This is the
    telemetry tap for ``repro.adaptive`` — the histogram describes
    exactly the symbols that went on the wire.
    """
    k = cfg.chunk_symbols
    *lead, m = x.shape
    assert m % k == 0, (m, k)
    n_chunks = m // k

    if cfg.enabled and cfg.use_kernels:
        from repro.kernels import ops as kops
        flat = x.reshape(-1, k).astype(jnp.float32)
        # emit_codes: the escape pool stores raw symbols of overflowing
        # chunks, so the wire assembly needs them once per chunk.
        outs = kops.quantize_encode(
            flat, tables, cfg.capacity_words, emit_codes=True,
            emit_hist=emit_hist)
        words, nbits, scales, chunk_codes = outs[:4]
        words = words.reshape(*lead, n_chunks, cfg.capacity_words)
        nbits = nbits.reshape(*lead, n_chunks)
        chunks = chunk_codes.reshape(*lead, n_chunks, k)
        scales = scales.reshape(*lead, m // e4m3.BLOCK).astype(cfg.scale_dtype)
        payload = _assemble_payload(chunks, words, nbits, cfg)
        if emit_hist:
            return payload, scales, outs[4]
        return payload, scales

    codes, scales = _quantize(x, cfg)
    payload = _compress_codes(codes, tables, cfg)
    if emit_hist:
        hist = jnp.bincount(codes.reshape(-1), length=256).astype(jnp.int32)
        return payload, scales, hist
    return payload, scales


def _pool_values(payload: WirePayload, scales: jnp.ndarray,
                 cfg: CommConfig):
    """Escape epilogue shared by the fused decode paths: dequantize ONLY
    the pool rows (O(pool_slots*K), not O(M)) — scatter each escaped
    chunk's scales to its slot, decode the raw pool bytes once, gather
    rows back per chunk.

    Returns ``(escape bool [..., n_chunks], raw_vals f32 [..., n_chunks,
    K], ok bool [...])``. Rows whose chunk did not escape (and, when the
    pool itself overflowed — ok=False, caller retries — rows beyond the
    pool) hold unspecified values; callers select with ``escape``.
    """
    k = cfg.chunk_symbols
    k32 = k // e4m3.BLOCK
    *lead, n_chunks, _ = payload.words.shape
    pool_slots = payload.pool.shape[-2]
    escape = payload.flags.astype(bool)
    esc_idx, slot = _escape_slots(payload.flags, pool_slots)
    chunk_scales = scales.astype(jnp.float32).reshape(*lead, n_chunks, k32)
    pool_scales = _scatter_pool_rows(chunk_scales, slot, pool_slots)

    pool_u8 = bytes_of_words(payload.pool)
    pool_vals = e4m3.dequantize_block32(
        pool_u8.reshape(*lead, pool_slots * k),
        pool_scales.reshape(*lead, pool_slots * k32),
    ).reshape(*lead, pool_slots, k)

    raw_vals = _gather_pool_rows(
        pool_vals, jnp.minimum(esc_idx, pool_slots - 1))
    ok = (payload.pool_count[..., 0] <= pool_slots)
    return escape, raw_vals, ok


def decompress_values(payload: WirePayload, scales: jnp.ndarray,
                      tables, cfg: CommConfig = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(WirePayload, scales) -> (float32 values [..., M], ok bool[...]).

    .. deprecated:: use ``Channel.decompress``.
    """
    _warn_legacy("decompress_values", "Channel.decompress")
    return _legacy_channel(tables, cfg).decompress(payload, scales)


def _decompress_values(payload: WirePayload, scales: jnp.ndarray,
                       tables: CodecTables, cfg: CommConfig
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolved-argument impl of :func:`decompress_values`.

    With ``cfg.use_kernels`` the QLC decode and e4m3 dequantize run as
    one fused Pallas dispatch producing floats directly from packed
    words; escaped chunks are dequantized from their raw pool form and
    selected in, which is elementwise identical to merging at the code
    level (dequantization is a per-symbol table gather times the block
    scale either way).
    """
    k = cfg.chunk_symbols
    *lead, n_chunks, _ = payload.words.shape

    if cfg.enabled and cfg.use_kernels:
        from repro.kernels import ops as kops
        k32 = k // e4m3.BLOCK
        flat_words = payload.words.reshape(-1, payload.words.shape[-1])
        flat_scales = scales.astype(jnp.float32).reshape(-1, k32)
        vals = kops.decode_dequantize(flat_words, flat_scales, tables, k)
        vals = vals.reshape(*lead, n_chunks, k)

        escape, raw_vals, ok = _pool_values(payload, scales, cfg)
        out = jnp.where(escape[..., None], raw_vals, vals)
        return out.reshape(*lead, n_chunks * k), ok

    codes, ok = _decompress_codes(payload, tables, cfg)
    return _dequantize(codes, scales), ok


def accumulate_values(acc: jnp.ndarray, payload: WirePayload,
                      scales: jnp.ndarray, tables, cfg: CommConfig = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``acc + decompress_values(payload)`` — the ring reduce-scatter's
    per-hop step. Returns ``(new_acc f32 [..., M], ok)``.

    .. deprecated:: use a ``Channel`` (the ring transport accumulates
    through ``transport._accumulate_row_pieces`` internally).
    """
    _warn_legacy("accumulate_values", "Channel collectives")
    tables, cfg = resolve_codec(tables, cfg)
    return _accumulate_values(acc, payload, scales, tables, cfg)


def _accumulate_values(acc: jnp.ndarray, payload: WirePayload,
                       scales: jnp.ndarray, tables: CodecTables,
                       cfg: CommConfig
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolved-argument impl of :func:`accumulate_values`.

    With ``cfg.use_kernels`` the decode, dequantize, AND the running sum
    run as ONE fused Pallas dispatch
    (``kernels.ops.decode_dequantize_accumulate``): the hop's decoded
    values never materialize in HBM, only the updated accumulator does.
    Escaped chunks merge through the shared pool epilogue at the
    accumulator level — ``where(escape, acc + raw, acc + decoded)`` —
    which is bit-identical to ``acc + where(escape, raw, decoded)``
    (f32 addition distributes over the elementwise select exactly).
    """
    k = cfg.chunk_symbols
    *lead, n_chunks, _ = payload.words.shape

    if cfg.enabled and cfg.use_kernels:
        from repro.kernels import ops as kops
        k32 = k // e4m3.BLOCK
        acc_rows = acc.reshape(-1, k).astype(jnp.float32)
        flat_words = payload.words.reshape(-1, payload.words.shape[-1])
        flat_scales = scales.astype(jnp.float32).reshape(-1, k32)
        summed = kops.decode_dequantize_accumulate(
            acc_rows, flat_words, flat_scales, tables, k)
        summed = summed.reshape(*lead, n_chunks, k)

        escape, raw_vals, ok = _pool_values(payload, scales, cfg)
        acc_chunks = acc.reshape(*lead, n_chunks, k)
        out = jnp.where(escape[..., None], acc_chunks + raw_vals, summed)
        return out.reshape(*lead, n_chunks * k), ok

    vals, ok = _decompress_values(payload, scales, tables, cfg)
    return acc + vals, ok


def pad_to_multiple(x: jnp.ndarray, multiple: int) -> Tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % multiple
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, n


# --------------------------------------------------------------------------
# Collectives (call inside shard_map with a named axis)
#
# DEPRECATED wrappers: each builds a one-shot Channel
# (repro.comm.channel) binding codec + transport + axis, then calls the
# corresponding method — the collective orchestration (padding,
# transport dispatch, valid-length accounting) lives on Channel now.
# Outputs are bit-identical to the pre-channel implementations; both
# transports remain bit-identical to each other (tested) — the reduce
# accumulation order is part of the transport contract (see
# transport._accumulate_row_pieces).
# --------------------------------------------------------------------------

class ReduceScatterResult(NamedTuple):
    """``qlc_reduce_scatter`` output.

    ``segment`` is the shard's summed segment, padded to the static
    segment length; ``valid`` (i32 scalar, traced) is how many leading
    entries of ``segment`` map to real (pre-padding) input on THIS
    shard — callers no longer re-derive it from ``cfg.chunk_symbols``
    and the axis geometry.
    """
    segment: jnp.ndarray     # f32 [seg_padded]
    valid: jnp.ndarray       # i32 [] — # of real entries in segment
    ok: jnp.ndarray          # bool []


def qlc_all_gather(x: jnp.ndarray, axis_name, tables,
                   cfg: CommConfig = None, *, transport=None,
                   axis_size: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-gather with e4m3+QLC wire. Returns (tiled gather f32 [D*n], ok).

    ``x`` is this shard's (float) payload; output is the concatenation of
    every peer's dequantized payload along axis 0 (flattened).
    ``tables`` is a ``CodecTables`` (explicit ``cfg``) or a registry
    ``CodecEntry`` (cfg from its plan) — same for every collective here.

    ``transport`` is ``None``/"oneshot" (legacy), "ring", or a planner
    :class:`~repro.comm.planner.TransportConfig`; the ring transport
    additionally needs the static ``axis_size``.

    .. deprecated:: use ``Channel.all_gather``.
    """
    _warn_legacy("qlc_all_gather", "Channel.all_gather")
    ch = _legacy_channel(tables, cfg, transport=transport,
                         axis_name=axis_name, axis_size=axis_size)
    return ch.all_gather(x)


def qlc_reduce_scatter(x: jnp.ndarray, axis_name, axis_size: int,
                       tables, cfg: CommConfig = None, *, transport=None
                       ) -> ReduceScatterResult:
    """Reduce-scatter(sum) with e4m3+QLC wire.

    Implemented as quantize-encode + exchange + decode-sum (the standard
    compressed-RS decomposition: compression must happen before the
    wire, so the reduction moves after the exchange). The one-shot
    transport exchanges via ``all_to_all``; the ring transport sends one
    original compressed segment per ``ppermute`` hop and folds it into
    the accumulator on arrival (fused decode→dequantize→accumulate
    dispatch when ``cfg.use_kernels``). Accumulation order is the ring
    arrival order on both transports, so they are bit-identical.

    Returns :class:`ReduceScatterResult` ``(segment, valid, ok)``; the
    segment is padded to the static length, ``valid`` counts its real
    entries. See ``qlc_psum`` for the round trip.

    .. deprecated:: use ``Channel.reduce_scatter``.
    """
    _warn_legacy("qlc_reduce_scatter", "Channel.reduce_scatter")
    ch = _legacy_channel(tables, cfg, transport=transport,
                         axis_name=axis_name, axis_size=axis_size)
    return ch.reduce_scatter(x)


def qlc_psum(x: jnp.ndarray, axis_name, axis_size: int, tables,
             cfg: CommConfig = None, *, transport=None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-reduce(sum) = compressed RS + compressed AG.

    Note both phases quantize (two e4m3 roundings), as in standard
    compressed all-reduce; the QLC coding itself adds zero error. The
    codec is resolved ONCE (by the channel) and threaded through both
    phases.

    .. deprecated:: use ``Channel.psum``.
    """
    _warn_legacy("qlc_psum", "Channel.psum")
    ch = _legacy_channel(tables, cfg, transport=transport,
                         axis_name=axis_name, axis_size=axis_size)
    return ch.psum(x)


def qlc_all_to_all(x: jnp.ndarray, axis_name, tables,
                   cfg: CommConfig = None, *, transport=None,
                   axis_size: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compressed all-to-all of x [D, ...] (row j -> peer j).

    .. deprecated:: use ``Channel.all_to_all``.
    """
    _warn_legacy("qlc_all_to_all", "Channel.all_to_all")
    # d is static from x.shape, so the legacy no-axis_size call keeps
    # working; Channel itself refuses a ring transport without it.
    ch = _legacy_channel(tables, cfg, transport=transport,
                         axis_name=axis_name,
                         axis_size=x.shape[0] if axis_size is None
                         else axis_size)
    return ch.all_to_all(x)


# --------------------------------------------------------------------------
# References (bf16 wire, no compression) for tests & baseline mode
# --------------------------------------------------------------------------

def ref_psum(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    return jax.lax.psum(x, axis_name)


def ref_all_gather(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    return jax.lax.all_gather(x.reshape(-1), axis_name).reshape(-1)


def ref_reduce_scatter(x: jnp.ndarray, axis_name, axis_size: int
                       ) -> jnp.ndarray:
    flat, _ = pad_to_multiple(x, axis_size)
    return jax.lax.psum_scatter(
        flat.reshape(axis_size, -1), axis_name, scatter_dimension=0,
        tiled=False).reshape(-1)
