"""Pallas TPU kernel: DMA double-buffered prefetch-decode.

The serving hot path pages KV blocks out of an HBM-resident container
arena. A synchronous decode puts the whole LUT decode on the critical
path at every block boundary; this kernel instead streams container
words tile-by-tile through a two-slot VMEM scratch with explicit
``make_async_copy`` DMAs, so tile k+1's words are in flight from HBM
while tile k LUT-decodes out of VMEM — the same overlap contract the
ring transport proves for collectives, pushed down into one dispatch.

Pipeline (grid step i over word tiles)::

      DMA   [t0 ========][t1 ========][t2 ========]
      decode            [t0 ========][t1 ========][t2 ========]
                         ^ wait sem(0)            ^ slots alternate

Step i starts the DMA for tile i+1 into slot ``(i+1) % 2``, waits on
slot ``i % 2``, then runs ``qlc_decode.decode_kernel`` on that slot
(stacked multi-LUT operands, per-chunk scheme slots). The words operand
therefore stays in ``pl.ANY`` (HBM) memory space — Pallas never
auto-copies it — and only 2 * tile_chunks * capacity_words * 4 bytes of
it are VMEM-resident at a time, independent of container size.

On CPU the kernel runs in interpret mode where the DMAs are synchronous
copies: bit-exact semantics, no overlap. Overlap is *measured* (not
assumed) by the serving-level prefetcher, which dispatches this decode
ahead of use and reports a trace-derived overlap fraction
(``kv_prefetch_overlap`` benchmark row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.qlc_decode import (LANES, area_table, decode_kernel,
                                      table_row)


def _prefetch_decode_kernel(words_hbm_ref, sid_ref, area_ref, dec_ref,
                            out_ref, vmem_ref, dma_sems, *,
                            chunk_symbols: int, prefix_bits: int,
                            n_area: int, n_tiles: int):
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)

    tc = vmem_ref.shape[1]

    def tile_copy(tile, into_slot):
        return pltpu.make_async_copy(
            words_hbm_ref.at[pl.ds(pl.multiple_of(tile * tc, tc), tc)],
            vmem_ref.at[into_slot], dma_sems.at[into_slot])

    # Warm-up: the first step issues its own DMA (no lookbehind exists).
    @pl.when(i == 0)
    def _():
        tile_copy(0, 0).start()

    # Prefetch: kick off tile i+1 into the other slot before we decode,
    # so the transfer runs under this tile's decode.
    @pl.when(i + 1 < n_tiles)
    def _():
        tile_copy(i + 1, jax.lax.rem(i + 1, 2)).start()

    tile_copy(i, slot).wait()
    decode_kernel(vmem_ref.at[slot], sid_ref, area_ref, dec_ref, out_ref,
                   chunk_symbols=chunk_symbols, prefix_bits=prefix_bits,
                   n_area=n_area)


@functools.partial(
    jax.jit,
    static_argnames=("chunk_symbols", "prefix_bits", "tile_chunks",
                     "interpret"))
def prefetch_decode_pallas(words: jnp.ndarray, scheme_ids: jnp.ndarray,
                           dec_lut: jnp.ndarray, area_sb: jnp.ndarray,
                           area_starts: jnp.ndarray,
                           *, chunk_symbols: int, prefix_bits: int,
                           tile_chunks: int,
                           interpret: bool) -> jnp.ndarray:
    """Decode [n_chunks, capacity_words] u32 slots -> [n_chunks, K] u8
    with the words streamed HBM -> VMEM through a double-buffered DMA.

    Bit-identical to :func:`repro.kernels.qlc_decode.decode_pallas`;
    only the word movement differs. n_chunks must be a multiple of
    tile_chunks (``ops.decode_block_async`` pads).
    """
    n_chunks, cw = words.shape
    assert n_chunks % tile_chunks == 0, (n_chunks, tile_chunks)
    assert dec_lut.ndim == 2 and area_sb.ndim == 2, (
        "stacked LUT operands required: dec_lut [S, 256], area_* [S, A]")
    n_tiles = n_chunks // tile_chunks
    # A DMA moves whole 128-lane tiles: pad the slot width to one.
    cw = -(-cw // LANES) * LANES
    words = jnp.pad(words, ((0, 0), (0, cw - words.shape[1])))
    atab = area_table(area_sb, area_starts)
    dtab = table_row(dec_lut, jnp.int32)

    kernel = functools.partial(
        _prefetch_decode_kernel, chunk_symbols=chunk_symbols,
        prefix_bits=prefix_bits, n_area=area_sb.shape[1], n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            # Words stay in HBM; the kernel DMAs tiles itself.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((tile_chunks, 1), lambda i: (i, 0)),
            pl.BlockSpec(atab.shape, lambda i: (0, 0)),
            pl.BlockSpec(dtab.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_chunks, chunk_symbols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, chunk_symbols), jnp.uint8),
        scratch_shapes=[
            pltpu.VMEM((2, tile_chunks, cw), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(words, scheme_ids, atab, dtab)
