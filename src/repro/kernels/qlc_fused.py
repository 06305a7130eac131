"""Fused Pallas TPU kernels: quantize→encode and decode→dequantize.

The unfused pipeline runs separate dispatches with HBM round-trips
between them::

    f32 --quantize--> u8 codes --(HBM)--> encode --> words

The fused encode kernel performs block-32 e4m3 quantization AND the QLC
bit-pack in one ``pallas_call``: the symbol tile never leaves VMEM. Per
tile of ``TILE_CHUNKS`` chunks it

  1. computes block-32 amax scales (``scale = amax / 480``, the paper's
     §3 block scaling) with a 5-step lane butterfly inside each 128-lane
     block, and quantizes ``x / scale`` to eXmY e4m3 with a branch-free
     bit-trick encoder (exponent extraction + one round-to-nearest-even
     per element — bit-exact against the table-search oracle in
     ``repro.quant.e4m3``, which tests enforce), into a VMEM scratch;
  2. bit-packs the scratch with the encoder of ``qlc_encode``;
  3. optionally emits the raw symbols (needed when the caller keeps an
     escape pool, e.g. the compressed collectives; ``ops`` derives the
     symbol histogram from them).

The mirror decode kernel runs the ``qlc_decode`` bit-window loop and
looks each symbol's index up in the stacked ``[S * 256]`` e4m3 *value*
table (the decoder LUT composed with the value table outside the
kernel), multiplying by the block scale in-register — decoded symbols
never touch HBM. Its optional accumulate form adds a running sum in the
same pass (the ring reduce-scatter's per-hop inner loop).

VMEM per program (TILE_CHUNKS=8, K=1024, CW=353): x f32 32 KiB, symbol
scratch 32 KiB, words 12 KiB, scales 1 KiB, tables ≈2 KiB.

``ops.quantize_encode`` / ``ops.decode_dequantize`` are the public
entry points (padding, table marshaling, tile autotuning, interpret
mode off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.qlc_decode import (LANES, area_table, block_loop,
                                      decode_rows, gather, lane_blocks,
                                      load_blocks, pad_lanes, table_row)
from repro.kernels.qlc_encode import code_table, pack_rows
from repro.quant.e4m3 import BLOCK, E4M3_MAX_FINITE

DEFAULT_TILE_CHUNKS = 8


# --------------------------------------------------------------------------
# In-kernel e4m3 quantization (bit-exact vs repro.quant.e4m3.e4m3_encode)
# --------------------------------------------------------------------------

def _e4m3_bits_encode(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> int32 e4m3 code, round-to-nearest-even, saturating.

    Branch-free equivalent of the oracle's 128-entry grid search: the
    float32 exponent field gives the e4m3 binade, one RTE rounding of
    ``mag / step`` gives the mantissa index (ties land on even codes
    because adjacent grid indices alternate parity, matching the
    oracle's tie-break). All-finite eXmY variant: NaN and overflow
    saturate to ±480; signed zero keeps its sign bit.
    """
    mag = jnp.abs(x)
    mag = jnp.where(jnp.isnan(mag), E4M3_MAX_FINITE, mag)
    mag = jnp.minimum(mag, E4M3_MAX_FINITE)
    bits = jax.lax.bitcast_convert_type(mag, jnp.uint32)
    e = (bits >> 23).astype(jnp.int32) - 127          # floor(log2(mag))
    e = jnp.maximum(e, -6)                            # subnormal binade
    step = jax.lax.bitcast_convert_type(
        ((e - 3 + 127) << 23).astype(jnp.uint32), jnp.float32)  # 2^(e-3)
    k = jnp.round(mag / step).astype(jnp.int32)       # RTE, k in [0, 16]
    carry = k == 16                                   # mantissa overflow
    e = jnp.where(carry, e + 1, e)
    k = jnp.where(carry, 8, k)
    code = jnp.where((e == -6) & (k < 8),             # subnormal codes 0..7
                     k, ((e + 7) << 3) | (k - 8))
    return jnp.where(jnp.signbit(x), code | 0x80, code)


def _quantize_block(x: jnp.ndarray):
    """(TC, 128) f32 -> (symbols i32, lane-broadcast block scales f32).

    Identical arithmetic to ``e4m3.quantize_block32`` (amax over aligned
    blocks of 32 lanes, ``scale = amax/480`` or 1 for zero blocks, one
    f32 divide), so the fused path is bit-exact against the unfused
    oracle. The amax is a lane butterfly: partners ``lane ^ s`` for
    s < 32 never leave their 32-lane block.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    amax = jnp.abs(x)
    for s in (1, 2, 4, 8, 16):
        amax = jnp.maximum(amax, jnp.take_along_axis(amax, lane ^ s, axis=1))
    # Same explicit reciprocal multiply as quantize_block32 (see the
    # comment there) — required for bit-exact fused/unfused parity.
    inv = np.float32(1.0) / np.float32(E4M3_MAX_FINITE)
    scale = jnp.where(amax > 0, amax * inv, 1.0)
    return _e4m3_bits_encode(x / scale), scale


# --------------------------------------------------------------------------
# Fused quantize -> encode
# --------------------------------------------------------------------------

def _fused_encode_kernel(x_ref, code_ref, words_ref, nbits_ref, scales_ref,
                         *rest, emit_codes: bool):
    codes_ref = rest[0] if emit_codes else None
    sym_ref = rest[-1]                                # (TC, K) i32 scratch
    tc, k = x_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (tc, LANES), 1)
    n_sregs = len(lane_blocks(k // BLOCK))

    def quantize(start, width, sregs):
        x = pad_lanes(x_ref[:, pl.ds(start, width)].astype(jnp.float32))
        sym, scale = _quantize_block(x)
        sym_ref[:, pl.ds(start, width)] = sym[:, :width]
        if emit_codes:
            codes_ref[:, pl.ds(start, width)] = (
                sym[:, :width].astype(jnp.uint8))
        # Lane 32q holds block q's scale. Its place in the compact
        # (TC, K/32) row is start/32 + q = 4j + q: register j >> 5, lane
        # 4 (j & 31) + q, whose lane index is ≡ q (mod 4).
        j = start // LANES
        g = jnp.take_along_axis(scale, (lane & 3) * BLOCK, axis=1)
        mine = ((lane >> 2) == (j & 31)) & ((lane & 3) < width // BLOCK)
        return tuple(jnp.where(mine & ((j >> 5) == r), g, s)
                     for r, s in enumerate(sregs))

    sregs = block_loop(
        k, quantize,
        tuple(jnp.zeros((tc, LANES), jnp.float32) for _ in range(n_sregs)))
    for (s, w), reg in zip(lane_blocks(k // BLOCK), sregs):
        scales_ref[:, s:s + w] = reg[:, :w]

    pack_rows(lambda start, width: pad_lanes(
        sym_ref[:, pl.ds(start, width)]), code_ref, words_ref, nbits_ref,
        chunk_symbols=k)


@functools.partial(
    jax.jit,
    static_argnames=("capacity_words", "tile_chunks", "emit_codes",
                     "interpret"))
def fused_encode_pallas(x: jnp.ndarray, enc_code: jnp.ndarray,
                        enc_len: jnp.ndarray, *, capacity_words: int,
                        tile_chunks: int, emit_codes: bool,
                        interpret: bool):
    """Quantize+encode [n_chunks, K] float -> packed QLC slots.

    Returns ``(words [n, CW] u32, nbits [n, 1] u32, scales [n, K/32]
    f32[, codes [n, K] u8])`` — codes only with ``emit_codes``.
    """
    n_chunks, k = x.shape
    assert n_chunks % tile_chunks == 0, (n_chunks, tile_chunks)
    assert k % BLOCK == 0, k
    ctab = code_table(enc_code, enc_len)
    kernel = functools.partial(_fused_encode_kernel, emit_codes=emit_codes)

    out_specs = [
        pl.BlockSpec((tile_chunks, capacity_words), lambda i: (i, 0)),
        pl.BlockSpec((tile_chunks, 1), lambda i: (i, 0)),
        pl.BlockSpec((tile_chunks, k // BLOCK), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_chunks, capacity_words), jnp.uint32),
        jax.ShapeDtypeStruct((n_chunks, 1), jnp.uint32),
        jax.ShapeDtypeStruct((n_chunks, k // BLOCK), jnp.float32),
    ]
    if emit_codes:
        out_specs.append(pl.BlockSpec((tile_chunks, k), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n_chunks, k), jnp.uint8))

    return pl.pallas_call(
        kernel,
        grid=(n_chunks // tile_chunks,),
        in_specs=[
            pl.BlockSpec((tile_chunks, k), lambda i: (i, 0)),
            pl.BlockSpec(ctab.shape, lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tile_chunks, k), jnp.int32)],
        interpret=interpret,
    )(x, ctab)


# --------------------------------------------------------------------------
# Fused decode -> dequantize
# --------------------------------------------------------------------------

def _fused_decode_kernel(words_ref, scales_ref, sid_ref, area_ref, val_ref,
                         *rest, chunk_symbols: int, prefix_bits: int,
                         n_area: int, accumulate: bool):
    acc_ref, out_ref = rest if accumulate else (None, rest[0])
    tc = words_ref.shape[0]
    vals = load_blocks(val_ref, tc)
    scales = load_blocks(scales_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tc, LANES), 1)

    def emit(start, width, idx):
        scale = gather(scales, start // BLOCK + (lane >> 5))
        flat = (gather(vals, idx) * scale)[:, :width]
        if accumulate:
            # The running sum of the ring reduce-scatter's per-hop
            # accumulate. The product goes through the output tile
            # first, so it rounds to f32 before the add (no FMA) and the
            # sum is bit-equal to decode-then-add.
            out_ref[:, pl.ds(start, width)] = flat
            flat = acc_ref[:, pl.ds(start, width)] + out_ref[
                :, pl.ds(start, width)]
        out_ref[:, pl.ds(start, width)] = flat.astype(out_ref.dtype)

    decode_rows(words_ref, sid_ref, area_ref, emit,
                chunk_symbols=chunk_symbols, prefix_bits=prefix_bits,
                n_area=n_area)


@functools.partial(
    jax.jit,
    static_argnames=("chunk_symbols", "prefix_bits", "tile_chunks",
                     "out_dtype", "interpret"))
def fused_decode_pallas(words: jnp.ndarray, scales: jnp.ndarray,
                        scheme_ids: jnp.ndarray, dec_lut: jnp.ndarray,
                        area_sb: jnp.ndarray, area_starts: jnp.ndarray,
                        value_tab: jnp.ndarray, acc: jnp.ndarray = None,
                        *, chunk_symbols: int, prefix_bits: int,
                        tile_chunks: int, out_dtype=jnp.float32,
                        interpret: bool) -> jnp.ndarray:
    """Decode+dequantize [n_chunks, CW] u32 slots -> [n_chunks, K] float.

    ``scales`` is [n_chunks, K/32] f32 (block-32 scales, chunk-major).
    ``scheme_ids`` is int32 [n_chunks, 1]: each chunk's slot into the
    stacked ``dec_lut [S, 256]`` / ``area_* [S, 2**prefix]`` operands
    (all-zero for single-scheme payloads). ``out_dtype`` (f32 default,
    bf16 for weight-wire consumers) is cast in-register before the
    store — same rounding as an external cast. n_chunks must be a
    multiple of tile_chunks (ops.py pads).

    ``acc`` ([n_chunks, K] f32, optional) switches the kernel to its
    fused decode→dequantize→accumulate form: the output becomes
    ``acc + decoded`` (f32 only) with the add performed in-register —
    the ring reduce-scatter's single-dispatch-per-hop inner loop.
    """
    n_chunks, cw = words.shape
    accumulate = acc is not None
    assert n_chunks % tile_chunks == 0, (n_chunks, tile_chunks)
    assert chunk_symbols % BLOCK == 0, chunk_symbols
    assert dec_lut.ndim == 2 and area_sb.ndim == 2, (
        "stacked LUT operands required: dec_lut [S, 256], area_* [S, A]")
    if accumulate:
        assert jnp.dtype(out_dtype) == jnp.dtype(jnp.float32), (
            "accumulate form is f32-only", out_dtype)
        assert acc.shape == (n_chunks, chunk_symbols), acc.shape
    atab = area_table(area_sb, area_starts)
    vtab = table_row(jnp.take(value_tab, dec_lut.astype(jnp.int32)),
                     jnp.float32)

    kernel = functools.partial(
        _fused_decode_kernel, chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits, n_area=area_sb.shape[1],
        accumulate=accumulate)
    in_specs = [
        pl.BlockSpec((tile_chunks, cw), lambda i: (i, 0)),
        pl.BlockSpec((tile_chunks, chunk_symbols // BLOCK),
                     lambda i: (i, 0)),
        pl.BlockSpec((tile_chunks, 1), lambda i: (i, 0)),
        pl.BlockSpec(atab.shape, lambda i: (0, 0)),
        pl.BlockSpec(vtab.shape, lambda i: (0, 0)),
    ]
    operands = [words, scales, scheme_ids, atab, vtab]
    if accumulate:
        in_specs.append(pl.BlockSpec((tile_chunks, chunk_symbols),
                                     lambda i: (i, 0)))
        operands.append(acc)

    return pl.pallas_call(
        kernel,
        grid=(n_chunks // tile_chunks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_chunks, chunk_symbols),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, chunk_symbols),
                                       out_dtype),
        interpret=interpret,
    )(*operands)
