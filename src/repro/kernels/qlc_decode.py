"""Pallas TPU kernel: chunk-parallel QLC decode (multi-LUT capable).

TPU-native adaptation of the paper's hardware decoder (DESIGN.md §3):
the 3-bit area code read from the bit window gives the code length in
O(1) — no tree walk — and throughput comes from decoding a tile of
chunks in lockstep (one chunk per sublane row; the loop over the K
symbols of a chunk is the only sequential dimension).

Every operation is one Mosaic accepts on a v5e. Per-row state (bit
cursor, current and next word, scheme slot) is kept lane-replicated as
``(TC, 128)`` arrays, and every lookup is a 128-lane ``take_along_axis``
(one ``tpu.dynamic_gather``) after a select over 128-lane blocks:

  * the chunk's words are loaded once as ``ceil(CW/128)`` lane blocks;
    each step fetches only the word after the cursor's (the window is
    ``cur | nxt``; a code of ≤ 11 bits advances the cursor ≤ 1 word);
  * area → (bits, first rank) is one packed lookup into the stacked
    ``[S * 2**prefix]`` area table;
  * the symbol lookup (``[S * 256]`` dec LUT, or its composition with
    the e4m3 value table in the fused decoder) runs once per 128
    symbols, vectorized, on the per-step ``sid * 256 + rank`` indices a
    select collects into one register — so nothing is stored one column
    at a time.

The LUT operands are **stacked per scheme** and every chunk carries a
scheme slot index (``sid``), so ONE dispatch decodes groups encoded
under different schemes (the paper's §7 multi-LUT deployment).

VMEM per program (TILE_CHUNKS=8, K=1024, CW=353): words 12 KiB,
out 8 KiB, tables ≈1 KiB per scheme — double-buffered well under
the 16 MiB scoped default of a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_CHUNKS = 8
LANES = 128


# --------------------------------------------------------------------------
# Lane-block helpers shared by every QLC kernel
# --------------------------------------------------------------------------

def lane_blocks(n: int):
    """Static (start, width) pieces of a length-``n`` lane axis."""
    return [(s, min(LANES, n - s)) for s in range(0, n, LANES)]


def pad_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad a ``(TC, w≤128)`` value to one full ``(TC, 128)`` block."""
    w = x.shape[-1]
    if w == LANES:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (LANES - w,), x.dtype)], axis=-1)


def load_blocks(ref, rows: int | None = None):
    """A ``(TC, n)`` ref as a list of ``(TC, 128)`` lane blocks; a
    ``(1, n)`` table ref is broadcast to ``rows`` sublanes."""
    out = []
    for s, w in lane_blocks(ref.shape[-1]):
        blk = pad_lanes(ref[:, s:s + w])
        if rows is not None:
            blk = jnp.broadcast_to(blk, (rows, LANES))
        out.append(blk)
    return out


def gather(blocks, idx: jnp.ndarray) -> jnp.ndarray:
    """Per-row ``row[idx]`` from a row stored as 128-lane blocks.

    ``idx`` is ``(TC, 128)`` int32, any index per lane: one in-vreg lane
    gather per block, then a select on the block number. Indices past
    the last block read block 0 (callers only do that for garbage
    slots).
    """
    hi, lo = idx >> 7, idx & (LANES - 1)
    out = jnp.take_along_axis(blocks[0], lo, axis=1)
    for b, blk in enumerate(blocks[1:], 1):
        out = jnp.where(hi == b, jnp.take_along_axis(blk, lo, axis=1), out)
    return out


def gather_uniform(blocks, idx: jnp.ndarray) -> jnp.ndarray:
    """:func:`gather` for an ``idx`` equal across each row's lanes (the
    decoder's lane-replicated state): the row's block is selected first,
    so it costs one lane gather whatever the block count."""
    hi = idx >> 7
    sel = blocks[0]
    for b, blk in enumerate(blocks[1:], 1):
        sel = jnp.where(hi == b, blk, sel)
    return jnp.take_along_axis(sel, idx & (LANES - 1), axis=1)


def table_row(tab: jnp.ndarray, dtype) -> jnp.ndarray:
    """Flatten a stacked LUT to the ``(1, n)`` operand ``load_blocks``
    reads (tiny; built outside the kernel)."""
    return tab.astype(dtype).reshape(1, -1)


def area_table(area_sb: jnp.ndarray, area_starts: jnp.ndarray):
    """Packed ``first_rank | bits << 16`` per (scheme, area) entry."""
    sb = area_sb.astype(jnp.int32)
    st = area_starts.astype(jnp.int32)
    return table_row(st | (sb << 16), jnp.int32)


def block_loop(n: int, body, carry):
    """Run ``body(start, width, carry)`` over the 128-lane pieces of an
    ``n``-long axis: full blocks in a ``fori_loop`` (aligned dynamic
    offsets), a short tail block statically."""
    n_full = n // LANES
    if n_full:
        def full(j, c):
            return body(pl.multiple_of(j * LANES, LANES), LANES, c)
        carry = jax.lax.fori_loop(0, n_full, full, carry)
    if n % LANES:
        carry = body(n_full * LANES, n % LANES, carry)
    return carry


# --------------------------------------------------------------------------
# Decode core
# --------------------------------------------------------------------------

def decode_rows(words_ref, sid_ref, area_ref, emit, *, chunk_symbols: int,
                prefix_bits: int, n_area: int):
    """Bit-window decode of a ``(TC, CW)`` words tile.

    For every 128-symbol block calls ``emit(start, width, idx)`` with
    ``idx`` the ``(TC, 128)`` int32 ``sid * 256 + rank`` of each decoded
    symbol — the index into the stacked ``[S * 256]`` symbol (or value)
    table. Lanes past ``width`` are garbage.
    """
    tc = words_ref.shape[0]
    wblocks = load_blocks(words_ref)
    atab = load_blocks(area_ref, tc)
    sid = jnp.broadcast_to(sid_ref[...].astype(jnp.int32), (tc, LANES))
    lane = jax.lax.broadcasted_iota(jnp.int32, (tc, LANES), 1)
    pmask = jnp.uint32((1 << prefix_bits) - 1)
    pbits = jnp.uint32(prefix_bits)

    def fetch(widx):
        return gather_uniform(wblocks, widx).astype(jnp.uint32)

    def step(i, state):
        bitpos, cur, nxt, idx = state
        shift = bitpos & jnp.uint32(31)
        window = (cur >> shift) | jnp.where(
            shift == 0, jnp.uint32(0), nxt << (jnp.uint32(32) - shift))
        area = (window & pmask).astype(jnp.int32)
        packed = gather_uniform(atab, sid * n_area + area)
        sb = packed >> 16
        payload = ((window >> pbits).astype(jnp.int32) & 0xFF) & (
            (1 << sb) - 1)
        rank = jnp.minimum((packed & 0xFFFF) + payload, 255)
        idx = jnp.where(lane == i, sid * 256 + rank, idx)
        new = bitpos + pbits + sb.astype(jnp.uint32)
        widx = (new >> 5).astype(jnp.int32)
        moved = widx != (bitpos >> 5).astype(jnp.int32)
        return (new, jnp.where(moved, nxt, cur), jnp.where(
            moved, fetch(widx + 1), nxt), idx)

    def block(start, width, state):
        state = jax.lax.fori_loop(0, width, step, state)
        emit(start, width, state[3])
        return state

    zeros = jnp.zeros((tc, LANES), jnp.int32)
    state = (jnp.zeros((tc, LANES), jnp.uint32), fetch(zeros),
             fetch(zeros + 1), zeros)
    block_loop(chunk_symbols, block, state)


def decode_kernel(words_ref, sid_ref, area_ref, dec_ref, out_ref, *,
                  chunk_symbols: int, prefix_bits: int, n_area: int):
    tc = words_ref.shape[0]
    dec = load_blocks(dec_ref, tc)

    def emit(start, width, idx):
        sym = gather(dec, idx)[:, :width]
        out_ref[:, pl.ds(start, width)] = sym.astype(out_ref.dtype)

    decode_rows(words_ref, sid_ref, area_ref, emit,
                chunk_symbols=chunk_symbols, prefix_bits=prefix_bits,
                n_area=n_area)


@functools.partial(
    jax.jit,
    static_argnames=("chunk_symbols", "prefix_bits", "tile_chunks",
                     "interpret"))
def decode_pallas(words: jnp.ndarray, scheme_ids: jnp.ndarray,
                  dec_lut: jnp.ndarray, area_sb: jnp.ndarray,
                  area_starts: jnp.ndarray,
                  *, chunk_symbols: int, prefix_bits: int,
                  tile_chunks: int, interpret: bool) -> jnp.ndarray:
    """Decode [n_chunks, capacity_words] u32 slots -> [n_chunks, K] u8.

    ``scheme_ids`` is int32 [n_chunks, 1] — each chunk's slot into the
    stacked ``dec_lut [S, 256]`` / ``area_* [S, 2**prefix]`` operands
    (all-zero for single-scheme decode). n_chunks must be a multiple of
    tile_chunks (ops.py pads).
    """
    n_chunks, cw = words.shape
    assert n_chunks % tile_chunks == 0, (n_chunks, tile_chunks)
    assert dec_lut.ndim == 2 and area_sb.ndim == 2, (
        "stacked LUT operands required: dec_lut [S, 256], area_* [S, A]")
    atab = area_table(area_sb, area_starts)
    dtab = table_row(dec_lut, jnp.int32)

    kernel = functools.partial(
        decode_kernel, chunk_symbols=chunk_symbols, prefix_bits=prefix_bits,
        n_area=area_sb.shape[1])
    return pl.pallas_call(
        kernel,
        grid=(n_chunks // tile_chunks,),
        in_specs=[
            pl.BlockSpec((tile_chunks, cw), lambda i: (i, 0)),
            pl.BlockSpec((tile_chunks, 1), lambda i: (i, 0)),
            pl.BlockSpec(atab.shape, lambda i: (0, 0)),
            pl.BlockSpec(dtab.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_chunks, chunk_symbols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, chunk_symbols), jnp.uint8),
        interpret=interpret,
    )(words, scheme_ids, atab, dtab)
