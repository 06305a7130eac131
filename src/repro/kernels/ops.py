"""jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples, table marshaling, tile-size
autotuning, and backend dispatch: on TPU the compiled kernels run
natively; elsewhere they run in interpret mode (bit-exact semantics)
so the whole framework is runnable and testable on CPU. That choice is
made here only (``_interpret_default``): the kernel functions take
``interpret`` as a required argument.

Entry points
------------
  encode / decode / histogram      — single-stage kernels.
  quantize_encode                  — fused float -> (words, nbits,
                                     scales[, codes][, hist]); the
                                     e4m3 quantization happens inside
                                     the kernel, symbols stay in VMEM.
  decode_dequantize                — fused words+scales -> float.
  decode_dequantize_accumulate     — fused words+scales+acc ->
                                     acc + float: decode, dequantize,
                                     and running-sum in ONE dispatch
                                     (the ring reduce-scatter's
                                     per-hop inner loop).

Both decode entry points take **per-group LUT operands**: ``tables``
may be a single ``CodecTables`` or a sequence of them, and
``scheme_ids`` (int [n_chunks]) assigns each chunk its scheme — one
dispatch decodes a payload whose groups were encoded under different
schemes (paper §7 multi-LUT deployment; see ``repro.core.registry``).

The fused pair is what the compressed collectives
(``repro.comm.compressed``), the weight wire (``repro.comm.weights``)
and the serving/checkpoint layers call on their hot paths.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as _codec
from repro.core.lut import CodecTables
from repro.kernels import qlc_decode, qlc_encode, qlc_fused
from repro.kernels import qlc_prefetch
from repro.kernels import histogram256 as _hist
from repro.quant import e4m3


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    return not _on_tpu()


# --------------------------------------------------------------------------
# Tile autotuning
# --------------------------------------------------------------------------

# tile_chunks per chunk-size bucket, from a VMEM working-set model
# (~20 B/symbol of per-chunk intermediates; target ≈512 KiB per program
# to leave headroom for double buffering): short chunks take more per
# tile, K=4096 drops to 2. ``tests/test_tpu_compile.py`` checks that the
# K=256 and K=1024 tiles compile within a v5e's VMEM; no tile size has
# been timed on a chip.
_TILE_CHUNKS_TABLE = {
    64: 32,
    128: 32,
    256: 16,
    512: 16,
    1024: 8,
    2048: 4,
    4096: 2,
}
_DEFAULT_TILE_CHUNKS = 8


def auto_tile_chunks(chunk_symbols: int, n_chunks: int | None = None) -> int:
    """Pick tile_chunks for a given chunk size (and optional row count).

    Looks up the nearest power-of-two bucket in the tuning table and
    caps the tile at the (padded) row count so tiny inputs don't pad
    8x. Callers can always override explicitly.
    """
    bucket = 1 << max(6, int(np.ceil(np.log2(max(chunk_symbols, 1)))))
    tile = _TILE_CHUNKS_TABLE.get(
        bucket,
        max(1, _TILE_CHUNKS_TABLE[1024] * 1024 // bucket))
    if n_chunks is not None and n_chunks > 0:
        cap = 1 << int(np.ceil(np.log2(n_chunks)))
        tile = min(tile, cap)
    return max(tile, 1)


def _pad_rows(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


# --------------------------------------------------------------------------
# Single-stage kernels
# --------------------------------------------------------------------------

def _stacked_luts(tables: CodecTables | Sequence[CodecTables]):
    """Marshal single or multiple CodecTables into stacked LUT operands."""
    tables_list = ([tables] if isinstance(tables, CodecTables)
                   else list(tables))
    dec, sb, st, prefix_bits = _codec.stack_decode_tables(tables_list)
    return (jnp.asarray(dec, dtype=jnp.int32),
            jnp.asarray(sb, dtype=jnp.int32),
            jnp.asarray(st, dtype=jnp.int32),
            prefix_bits, len(tables_list))


def _sid_rows(scheme_ids, n_chunks: int, n_schemes: int,
              tile_chunks: int) -> jnp.ndarray:
    """Per-chunk scheme slots as the kernels' [n_padded, 1] i32 operand."""
    if scheme_ids is None:
        sid = jnp.zeros((n_chunks,), jnp.int32)
    else:
        sid = jnp.asarray(scheme_ids, jnp.int32).reshape(-1)
        assert sid.shape[0] == n_chunks, (sid.shape, n_chunks)
    # Callers pass slots < n_schemes; the kernels do not check.
    del n_schemes
    return _pad_rows(sid[:, None], tile_chunks)


def decode(words: jnp.ndarray,
           tables: CodecTables | Sequence[CodecTables],
           chunk_symbols: int, *, scheme_ids=None,
           tile_chunks: int | None = None, interpret: bool | None = None
           ) -> jnp.ndarray:
    """Decode [n_chunks, CW] u32 -> [n_chunks, K] u8 via the Pallas kernel.

    ``tables`` may be a sequence of CodecTables with ``scheme_ids``
    (int [n_chunks]) selecting each chunk's scheme — multi-LUT batched
    decode in one dispatch.
    """
    if interpret is None:
        interpret = _interpret_default()
    n_chunks = words.shape[0]
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(chunk_symbols, n_chunks)
    dec, sb, st, prefix_bits, n_schemes = _stacked_luts(tables)
    padded = _pad_rows(words, tile_chunks)
    sid = _sid_rows(scheme_ids, n_chunks, n_schemes, tile_chunks)
    out = qlc_decode.decode_pallas(
        padded, sid, dec, sb, st,
        chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits,
        tile_chunks=tile_chunks,
        interpret=interpret,
    )
    return out[:n_chunks]


def decode_block_async(words: jnp.ndarray,
                       tables: CodecTables | Sequence[CodecTables],
                       chunk_symbols: int, *, scheme_ids=None,
                       tile_chunks: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Decode [n_chunks, CW] u32 -> [n_chunks, K] u8 via the DMA
    double-buffered prefetch kernel (``kernels/qlc_prefetch.py``).

    Bit-identical to :func:`decode`; the difference is word movement:
    the container words stay in HBM (``ANY`` memory space) and stream
    tile-by-tile through a two-slot VMEM scratch, so tile k+1's DMA
    runs under tile k's LUT decode. This is the device half of the
    serving prefetcher — the entry point `PagedKVCache` dispatches
    ahead of block use.
    """
    if interpret is None:
        interpret = _interpret_default()
    n_chunks = words.shape[0]
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(chunk_symbols, n_chunks)
    dec, sb, st, prefix_bits, n_schemes = _stacked_luts(tables)
    padded = _pad_rows(words, tile_chunks)
    sid = _sid_rows(scheme_ids, n_chunks, n_schemes, tile_chunks)
    out = qlc_prefetch.prefetch_decode_pallas(
        padded, sid, dec, sb, st,
        chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits,
        tile_chunks=tile_chunks,
        interpret=interpret,
    )
    return out[:n_chunks]


def encode(symbols: jnp.ndarray, tables: CodecTables, capacity_words: int,
           *, tile_chunks: int | None = None, interpret: bool | None = None):
    """Encode [n_chunks, K] u8 -> ([n_chunks, CW] u32, [n_chunks] u32)."""
    if interpret is None:
        interpret = _interpret_default()
    n_chunks, k = symbols.shape
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(k, n_chunks)
    padded = _pad_rows(symbols, tile_chunks)
    words, nbits = qlc_encode.encode_pallas(
        padded,
        jnp.asarray(tables.enc_code, dtype=jnp.uint32),
        jnp.asarray(tables.enc_len, dtype=jnp.uint32),
        capacity_words=capacity_words,
        tile_chunks=tile_chunks,
        interpret=interpret,
    )
    return words[:n_chunks], nbits[:n_chunks, 0]


def histogram(symbols: jnp.ndarray, *, tile_rows: int = 8,
              interpret: bool | None = None) -> jnp.ndarray:
    """uint8 array (any shape) -> [256] int32 counts via the Pallas kernel."""
    if interpret is None:
        interpret = _interpret_default()
    flat = symbols.reshape(-1)
    lanes = 128
    pad = (-flat.shape[0]) % (lanes * tile_rows)
    # Pad with zeros, then subtract the padding from bin 0.
    padded = jnp.pad(flat, (0, pad))
    mat = padded.reshape(-1, lanes)
    counts = _hist.histogram256_pallas(
        mat, tile_rows=tile_rows, interpret=interpret)
    return counts.at[0].add(-pad)


# --------------------------------------------------------------------------
# Fused pipeline
# --------------------------------------------------------------------------

def quantize_encode(x: jnp.ndarray, tables: CodecTables,
                    capacity_words: int, *, tile_chunks: int | None = None,
                    emit_codes: bool = False, emit_hist: bool = False,
                    interpret: bool | None = None):
    """Fused e4m3-quantize + QLC-encode of float chunks.

    Args:
      x: float [n_chunks, K] (f32/bf16; K divisible by 32).
      tables: codec tables.
      capacity_words: slot size per chunk in 32-bit words.
      emit_codes: also return the raw e4m3 symbols (escape-pool callers).
      emit_hist: also return the 256-bin symbol histogram (counted from
        the kernel's symbol output).

    Returns:
      (words u32 [n, CW], nbits u32 [n], scales f32 [n, K/32]
       [, codes u8 [n, K]] [, hist i32 [256]]).
    """
    if interpret is None:
        interpret = _interpret_default()
    n_chunks, k = x.shape
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(k, n_chunks)
    padded = _pad_rows(x, tile_chunks)
    outs = qlc_fused.fused_encode_pallas(
        padded,
        jnp.asarray(tables.enc_code, dtype=jnp.uint32),
        jnp.asarray(tables.enc_len, dtype=jnp.uint32),
        capacity_words=capacity_words,
        tile_chunks=tile_chunks,
        emit_codes=emit_codes or emit_hist,
        interpret=interpret,
    )
    words, nbits, scales = outs[:3]
    result = [words[:n_chunks], nbits[:n_chunks, 0], scales[:n_chunks]]
    if emit_codes:
        result.append(outs[3][:n_chunks])
    if emit_hist:
        result.append(jnp.bincount(outs[3][:n_chunks].reshape(-1),
                                   length=256).astype(jnp.int32))
    return tuple(result)


def decode_dequantize(words: jnp.ndarray, scales: jnp.ndarray,
                      tables: CodecTables | Sequence[CodecTables],
                      chunk_symbols: int, *, scheme_ids=None,
                      tile_chunks: int | None = None,
                      out_dtype=jnp.float32,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused QLC-decode + e4m3-dequantize.

    Args:
      words: u32 [n_chunks, CW] packed slots.
      scales: f32 [n_chunks, K/32] block-32 scales (chunk-major).
      tables: codec tables — one ``CodecTables`` or a sequence of them
        (per-group LUT operands).
      chunk_symbols: K.
      scheme_ids: int [n_chunks] slot of each chunk's scheme into
        ``tables`` when a sequence is given (multi-LUT batched decode).
      out_dtype: output float dtype (f32 default; bf16 casts in-kernel).

    Returns:
      [n_chunks, K] dequantized values, bit-exact against ``decode``
      followed by ``e4m3.dequantize_block32`` (plus the output cast).
    """
    if interpret is None:
        interpret = _interpret_default()
    n_chunks = words.shape[0]
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(chunk_symbols, n_chunks)
    dec, sb, st, prefix_bits, n_schemes = _stacked_luts(tables)
    padded_w = _pad_rows(words, tile_chunks)
    padded_s = _pad_rows(scales.astype(jnp.float32), tile_chunks)
    sid = _sid_rows(scheme_ids, n_chunks, n_schemes, tile_chunks)
    out = qlc_fused.fused_decode_pallas(
        padded_w, padded_s, sid, dec, sb, st,
        jnp.asarray(e4m3.decode_table(), dtype=jnp.float32),
        chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits,
        tile_chunks=tile_chunks,
        out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:n_chunks]


def decode_dequantize_accumulate(acc: jnp.ndarray, words: jnp.ndarray,
                                 scales: jnp.ndarray,
                                 tables: CodecTables | Sequence[CodecTables],
                                 chunk_symbols: int, *, scheme_ids=None,
                                 tile_chunks: int | None = None,
                                 interpret: bool | None = None
                                 ) -> jnp.ndarray:
    """Fused QLC-decode + e4m3-dequantize + accumulate: one dispatch
    per ring reduce-scatter hop.

    Args:
      acc: f32 [n_chunks, K] running accumulator.
      words: u32 [n_chunks, CW] packed slots of the arriving hop.
      scales: f32 [n_chunks, K/32] block-32 scales of the hop.
      tables / scheme_ids: as in :func:`decode_dequantize`.

    Returns:
      [n_chunks, K] f32 ``acc + dequantize(decode(words))``, bit-exact
      against ``decode_dequantize`` followed by a separate add: the
      kernel rounds the dequantize product through its output tile
      before adding, so no FMA contraction can keep excess precision.
      Transport-level bit-identity still comes from running the SAME
      accumulate op sequence on every path
      (``transport._accumulate_row_pieces``).
    """
    if interpret is None:
        interpret = _interpret_default()
    n_chunks = words.shape[0]
    assert acc.shape == (n_chunks, chunk_symbols), (
        acc.shape, n_chunks, chunk_symbols)
    if tile_chunks is None:
        tile_chunks = auto_tile_chunks(chunk_symbols, n_chunks)
    dec, sb, st, prefix_bits, n_schemes = _stacked_luts(tables)
    padded_w = _pad_rows(words, tile_chunks)
    padded_s = _pad_rows(scales.astype(jnp.float32), tile_chunks)
    padded_a = _pad_rows(acc.astype(jnp.float32), tile_chunks)
    sid = _sid_rows(scheme_ids, n_chunks, n_schemes, tile_chunks)
    out = qlc_fused.fused_decode_pallas(
        padded_w, padded_s, sid, dec, sb, st,
        jnp.asarray(e4m3.decode_table(), dtype=jnp.float32),
        padded_a,
        chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits,
        tile_chunks=tile_chunks,
        out_dtype=jnp.float32,
        interpret=interpret,
    )
    return out[:n_chunks]
