"""Pallas TPU kernel: chunk-parallel QLC encode.

One chunk per sublane row. Per 128-symbol block the kernel looks up
each symbol's packed ``code | len << 16`` in the 256-entry encoder LUT
(vectorized), then walks the block's symbols in order, appending each
code to a 32-bit bit buffer. A full buffer is emitted as the next slot
word by a select into one of ``ceil(CW/128)`` lane-block registers —
the bit-serial form of the reference's exclusive prefix sum plus two
scatter-adds, with no scatter, lane ``cumsum`` or 1-D gather, none of
which Mosaic lowers.

Overflowing chunks keep the reference's slot contents exactly: the
reference clamps every word index to ``capacity_words - 1`` and adds,
so the last slot word is the wrapping sum of every "natural" word at or
past it. ``nbits`` is the exact encoded length either way.

VMEM per program (TILE_CHUNKS=8, K=1024, CW=353): symbols 8 KiB,
words 12 KiB, table 1 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qlc_decode import (LANES, block_loop, gather,
                                      lane_blocks, load_blocks, pad_lanes,
                                      table_row)

DEFAULT_TILE_CHUNKS = 8


def code_table(enc_code: jnp.ndarray, enc_len: jnp.ndarray) -> jnp.ndarray:
    """Packed ``code | len << 16`` encoder LUT as a ``(1, 256)`` row."""
    return table_row(enc_code.astype(jnp.int32)
                     | (enc_len.astype(jnp.int32) << 16), jnp.int32)


def pack_rows(sym_block, code_ref, words_ref, nbits_ref, *,
              chunk_symbols: int):
    """Bit-pack ``(TC, K)`` symbols into the ``(TC, CW)`` words tile.

    ``sym_block(start, width)`` returns the ``(TC, 128)`` int32 symbols
    of one lane block (lanes past ``width`` ignored).
    """
    tc, cw = words_ref.shape
    ctab = load_blocks(code_ref, tc)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tc, LANES), 1)
    last = cw - 1

    def put(word, at, out, tail, emit):
        """Where ``emit``, store ``word`` as natural slot word ``at``."""
        keep = emit & (at < last)
        out = tuple(
            jnp.where(keep & ((at >> 7) == b) & (lane == (at & 127)),
                      word, blk)
            for b, blk in enumerate(out))
        return out, tail + jnp.where(emit & (at >= last), word,
                                     jnp.uint32(0))

    def step(i, state, packed):
        fill, buf, at, out, tail = state
        p = jnp.take_along_axis(packed, jnp.full((tc, LANES), i, jnp.int32),
                                axis=1)
        code = (p & 0xFFFF).astype(jnp.uint32)
        word = buf | (code << fill)
        spill = jnp.where(fill == 0, jnp.uint32(0),
                          code >> (jnp.uint32(32) - fill))
        fill = fill + (p >> 16).astype(jnp.uint32)
        full = fill >= 32
        out, tail = put(word, at, out, tail, full)
        return (jnp.where(full, fill - 32, fill), jnp.where(full, spill, word),
                at + full.astype(jnp.int32), out, tail)

    def block(start, width, state):
        packed = gather(ctab, sym_block(start, width))
        return jax.lax.fori_loop(
            0, width, lambda i, s: step(i, s, packed), state)

    zero_u = jnp.zeros((tc, LANES), jnp.uint32)
    n_out = len(lane_blocks(cw))
    state = (zero_u, zero_u, jnp.zeros((tc, LANES), jnp.int32),
             (zero_u,) * n_out, zero_u)
    fill, buf, at, out, tail = block_loop(chunk_symbols, block, state)
    out, tail = put(buf, at, out, tail, at >= 0)      # the partial word
    lw = jnp.full((tc, LANES), last, jnp.int32)
    out = tuple(jnp.where(((lw >> 7) == b) & (lane == (last & 127)), tail, o)
                for b, o in enumerate(out))
    for (s, w), blk in zip(lane_blocks(cw), out):
        words_ref[:, s:s + w] = blk[:, :w]
    nbits_ref[...] = ((at.astype(jnp.uint32) << 5) + fill)[:, :1]


def _encode_kernel(sym_ref, code_ref, words_ref, nbits_ref, *,
                   chunk_symbols: int):
    def sym_block(start, width):
        return pad_lanes(sym_ref[:, pl.ds(start, width)].astype(jnp.int32))

    pack_rows(sym_block, code_ref, words_ref, nbits_ref,
              chunk_symbols=chunk_symbols)


@functools.partial(
    jax.jit,
    static_argnames=("capacity_words", "tile_chunks", "interpret"))
def encode_pallas(symbols: jnp.ndarray, enc_code: jnp.ndarray,
                  enc_len: jnp.ndarray, *, capacity_words: int,
                  tile_chunks: int, interpret: bool):
    """Encode [n_chunks, K] u8 -> ([n_chunks, CW] u32, [n_chunks, 1] u32)."""
    n_chunks, k = symbols.shape
    assert n_chunks % tile_chunks == 0, (n_chunks, tile_chunks)
    ctab = code_table(enc_code, enc_len)
    kernel = functools.partial(_encode_kernel, chunk_symbols=k)
    return pl.pallas_call(
        kernel,
        grid=(n_chunks // tile_chunks,),
        in_specs=[
            pl.BlockSpec((tile_chunks, k), lambda i: (i, 0)),
            pl.BlockSpec(ctab.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_chunks, capacity_words), lambda i: (i, 0)),
            pl.BlockSpec((tile_chunks, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks, capacity_words), jnp.uint32),
            jax.ShapeDtypeStruct((n_chunks, 1), jnp.uint32),
        ],
        interpret=interpret,
    )(symbols, ctab)
