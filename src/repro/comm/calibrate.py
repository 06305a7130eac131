"""Per-tensor-type codec calibration (paper §7: one LUT per tensor type,
derived apriori from a histogram of the quantized data).

Typical flow: run one (uncompressed) step, histogram the e4m3 symbols of
the tensors you intend to compress, build tables + wire plan. The
histogram kernel (``repro.kernels.ops.histogram``) does this on-device
for production; here numpy suffices.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.planner import CommPlan, plan_for_tables
from repro.core import adapt
from repro.core.lut import CodecTables
from repro.core.schemes import QLCScheme
from repro.quant import e4m3


# Symbols quantized per call (a multiple of the block size). On a TPU
# the [n/32, 32] block view pads to 128 lanes, 4x the f32 bytes, so a
# whole gradient vector at once would not fit beside the model.
_PIECE = 1 << 24


def quantized_symbols(x: jnp.ndarray) -> np.ndarray:
    """float tensor -> its block-32 e4m3 symbols, flat ``uint8`` on the
    host (a trailing partial block is dropped), quantized a piece at a
    time."""
    flat = jnp.asarray(x, jnp.float32).reshape(-1)
    n = (flat.shape[0] // e4m3.BLOCK) * e4m3.BLOCK
    return np.concatenate([np.zeros(0, np.uint8)] + [
        np.asarray(e4m3.quantize_block32(flat[i:min(i + _PIECE, n)])[0])
        .reshape(-1) for i in range(0, n, _PIECE)])


def histogram_of_quantized(x: jnp.ndarray) -> np.ndarray:
    """float tensor -> counts[256] of its block-32 e4m3 symbols."""
    return np.bincount(quantized_symbols(x),
                       minlength=256).astype(np.float64)


def histogram_of_tree(tree) -> np.ndarray:
    """Pytree of float tensors -> summed counts[256] of their e4m3
    symbols, accumulated leaf by leaf (no concatenated f32 copy of the
    whole tree). The parameter-type calibration input for
    ``CodecRegistry.register("params", ...)``."""
    counts = np.zeros(256, dtype=np.float64)
    for leaf in jax.tree.leaves(tree):
        counts += histogram_of_quantized(leaf)
    return counts


def calibrate_for_tensor(x: jnp.ndarray, scheme: Optional[QLCScheme] = None,
                         chunk_symbols: int = 1024,
                         target_escape_prob: float = 1e-6,
                         allow_search: bool = False,
                         empirical: bool = True,
                         ) -> Tuple[CodecTables, CommPlan]:
    """Histogram a representative tensor and derive tables + wire plan.

    ``empirical=True`` sizes the chunk slot from the *measured* per-chunk
    bit-count distribution rather than an iid Hoeffding bound. Real
    payloads (e.g. a whole gradient vector) are mixtures of tensor types
    with very different local statistics, so chunk sums are far more
    dispersed than iid sampling of the global PMF predicts; the quantile
    + margin sizing keeps the escape rate at the target without giving
    up the compressible bulk. (The paper's per-tensor-type LUTs, §7, are
    the other half of the answer — the planner supports one plan per
    tensor type.)
    """
    codes_np = quantized_symbols(x)
    counts = np.maximum(
        np.bincount(codes_np, minlength=256).astype(np.float64), 1e-6)
    tables = adapt.calibrate_tables(counts, scheme=scheme,
                                    allow_search=allow_search)
    plan = plan_for_tables(tables, counts, chunk_symbols=chunk_symbols,
                           target_escape_prob=target_escape_prob)
    if empirical:
        plan = empirical_plan(tables, codes_np, plan,
                              chunk_symbols=chunk_symbols,
                              target_escape_prob=target_escape_prob)
    return tables, plan


def empirical_plan(tables: CodecTables, syms: np.ndarray, plan: CommPlan,
                   *, chunk_symbols: int = 1024,
                   target_escape_prob: float = 1e-6,
                   max_pool_slots_per_1k: Optional[int] = None,
                   drift_margin_bits: Optional[float] = None) -> CommPlan:
    """Re-size a plan's chunk slot from the *measured* per-chunk
    bit-count distribution of a representative symbol stream.

    Real payloads are mixtures of local statistics (tensor types,
    byte planes), so chunk sums are more dispersed than iid sampling
    of the global PMF predicts; the 99.9th-percentile + drift-margin
    sizing keeps the escape rate at the target without giving up the
    compressible bulk. Streams shorter than 8 chunks keep the iid plan.

    ``max_pool_slots_per_1k`` caps the escape pool for callers that
    have a raw-wire fallback for incompressible streams (the paged KV
    cache) — an uncapped near-uniform byte stream would otherwise size
    a pool bigger than its payload. The default (no cap) keeps the
    collectives' guarantee that the pool covers the measured escape
    rate.

    The per-symbol headroom added above the measured 99.9th percentile
    is the incoming plan's ``drift_margin_bits`` (the ONE per-entry
    field recording intended drift headroom — set it via
    ``plan_for_tables(drift_margin_bits=...)``); the keyword here is an
    explicit override. The 0.5-bit default suits gradient streams,
    whose chunk sums have heavy tails that keep moving over training.
    Streams whose chunk-sum distribution *plateaus* — e.g. MoE dispatch
    buffers, where capacity padding makes the distribution bimodal and
    the all-token mode sits at the e4m3 code's bounded expected length,
    so p99.9 ~= max — carry a smaller margin and let the escape pool
    absorb residual drift. The margin is preserved on the returned
    plan (and registry-JSON round-tripped), so the adaptive drift
    policy reads the same headroom the slot was sized with.
    """
    if drift_margin_bits is None:
        drift_margin_bits = plan.drift_margin_bits
    syms = np.asarray(syms).reshape(-1)
    lens = tables.enc_len[syms].astype(np.int64)
    n_chunks = len(lens) // chunk_symbols
    if n_chunks < 8:
        return plan
    sums = lens[:n_chunks * chunk_symbols].reshape(
        n_chunks, chunk_symbols).sum(axis=1)
    # 99.9th percentile + per-symbol drift margin
    q = float(np.quantile(sums, 0.999))
    bits = min(8.0 * chunk_symbols, q + drift_margin_bits * chunk_symbols)
    cap_words = max(1, int(np.ceil(bits / 32)))
    emp_escape = float((sums > cap_words * 32).mean())
    pool = max(8, int(np.ceil(emp_escape * 1024 * 8)) + 8)
    if max_pool_slots_per_1k is not None:
        pool = min(max_pool_slots_per_1k, pool)
    return CommPlan(
        chunk_symbols=chunk_symbols,
        capacity_words=cap_words,
        pool_slots_per_1k=pool,
        expected_bits_per_symbol=plan.expected_bits_per_symbol,
        escape_prob_bound=max(emp_escape, target_escape_prob),
        drift_margin_bits=drift_margin_bits,
    )


def calibrate_for_gradients(model_cfg, params, batch,
                            chunk_symbols: int = 1024,
                            allow_search: bool = False,
                            ) -> Tuple[CodecTables, CommPlan]:
    """One backward pass -> gradient histogram -> tables + plan."""
    from repro.models import next_token_loss  # local import (cycle)

    def loss(p):
        return next_token_loss(p, model_cfg, batch["tokens"],
                               batch["labels"], batch.get("prefix_emb"))

    grads = jax.grad(loss)(params)
    flat = jnp.concatenate([g.reshape(-1).astype(jnp.float32)
                            for g in jax.tree.leaves(grads)])
    return calibrate_for_tensor(flat, chunk_symbols=chunk_symbols,
                                allow_search=allow_search)


# --------------------------------------------------------------------------
# Per-layer KV / SSM-state codecs (serving paged cache)
# --------------------------------------------------------------------------

def kv_symbol_stream(arrays, mode: str = "qlc") -> np.ndarray:
    """Decode-state arrays -> the uint8 symbol stream the KV codec sees.

    ``mode="qlc"`` (lossless): the arrays' raw bytes ARE the symbols —
    the checkpoint manager's byte-width trick extended to wider dtypes,
    so encode→decode is bit-exact and serving output is token-identical
    to a dense cache. ``mode="e4m3"``: block-32 e4m3 symbols of the
    values (the fp8-cache trade: quantization is lossy once, the QLC
    coding on top is not).
    """
    if mode == "e4m3":
        return np.concatenate([np.zeros(0, np.uint8)]
                              + [quantized_symbols(a) for a in arrays])
    return np.concatenate(
        [np.ascontiguousarray(np.asarray(a)).view(np.uint8).reshape(-1)
         for a in arrays]) if arrays else np.zeros(0, np.uint8)


def byte_planes(arrays) -> Dict[Tuple[int, int], np.ndarray]:
    """Byte-plane decomposition of float state arrays (lossless mode's
    symbol streams).

    Little-endian byte *j* of every ``itemsize``-wide value, pooled
    across arrays in order: ``{(itemsize, j): uint8 stream}``. A
    float's planes have wildly different entropy — sign/exponent bytes
    code down to a few bits, mantissa bytes are near-uniform — so one
    interleaved stream wastes slot capacity on the worst plane, while
    per-plane containers (each with its own calibrated LUT and
    measured capacity, raw where the codec cannot win) compress the
    compressible planes without the mantissa dragging them down.
    """
    groups: Dict[int, list] = {}
    for a in arrays:
        isz = np.dtype(np.asarray(a).dtype).itemsize
        b = np.ascontiguousarray(np.asarray(a)).view(np.uint8)
        groups.setdefault(isz, []).append(b.reshape(-1, isz))
    out: Dict[Tuple[int, int], np.ndarray] = {}
    for isz in sorted(groups):
        mat = np.concatenate(groups[isz], axis=0)        # [n_values, isz]
        for j in range(isz):
            out[(isz, j)] = np.ascontiguousarray(mat[:, j])
    return out


def calibrate_moe_entries(registry, model_cfg, params, batch, *,
                          chunk_symbols: int = 1024,
                          target_escape_prob: float = 1e-4,
                          dispatch_name: str = "moe/dispatch",
                          combine_name: str = "moe/combine",
                          allow_search: bool = False) -> Dict[str, "object"]:
    """Calibrate the MoE expert-dispatch wire codecs into ``registry``.

    Runs ONE eager forward pass over ``batch`` with traffic capture on
    (``moe.capture_moe_traffic``), recomputes each captured MoE layer's
    dispatch/combine buffers via ``moe.dispatch_traffic`` — the actual
    routed-token values entering/leaving the expert ``all_to_all``,
    capacity drops and padding zeros included — and registers one codec
    per direction from the pooled e4m3-symbol histograms:

    * ``dispatch_name`` — pre-FFN token activations (a2a out),
    * ``combine_name`` — post-FFN expert outputs (a2a back).

    The two distributions differ (the FFN reshapes the value histogram),
    which is why they get separate LUTs + slot plans (paper §7's
    per-tensor-type rule applied per collective). Names already in
    ``registry`` are kept (idempotent). Returns ``{name: CodecEntry}``.

    The capture forward runs with ``use_scan=False``/``remat="none"``
    (scan traces its body even when called eagerly) and
    ``moe.impl="gspmd"`` (no mesh needed) — routing is impl-invariant,
    so the histograms apply to the ``shardmap_a2a`` wire unchanged.
    """
    from repro.models import moe, next_token_loss  # local import (cycle)

    todo = [n for n in (dispatch_name, combine_name) if n not in registry]
    if not todo:
        return {dispatch_name: registry[dispatch_name],
                combine_name: registry[combine_name]}

    eager_cfg = dataclasses.replace(
        model_cfg, use_scan=False, remat="none",
        moe=dataclasses.replace(model_cfg.moe, impl="gspmd"))
    captured: list = []
    with moe.capture_moe_traffic(captured):
        next_token_loss(params, eager_cfg, batch["tokens"],
                        batch["labels"], batch.get("prefix_emb"))
    if not captured:
        raise ValueError(
            "no MoE traffic captured — is model_cfg.moe set (and the "
            "forward eager)?")

    streams = {dispatch_name: [], combine_name: []}
    for layer_params, x in captured:
        buf, out_e = moe.dispatch_traffic(layer_params, x, eager_cfg)
        streams[dispatch_name].append(buf)
        streams[combine_name].append(out_e)

    entries = {}
    for name in (dispatch_name, combine_name):
        if name not in todo:
            entries[name] = registry[name]
            continue
        syms = kv_symbol_stream(streams[name], mode="e4m3")
        counts = np.maximum(
            np.bincount(syms, minlength=256).astype(np.float64), 1e-6)
        tables = adapt.calibrate_tables(counts, allow_search=allow_search)
        # Padding zeros make routed-token buffers bimodal; size the
        # slot from measured chunk sums. The chunk-sum distribution
        # plateaus at the all-token mode (p99.9 ~= max), so a quarter-
        # bit drift margin suffices — the capped escape pool and the
        # a2a wire's ok flag cover the residual tail. Recording the
        # margin on the plan (rather than passing it ad hoc) lets the
        # drift policy read the same headroom the slot was sized with.
        plan = plan_for_tables(tables, counts, chunk_symbols=chunk_symbols,
                               target_escape_prob=target_escape_prob,
                               drift_margin_bits=0.25)
        plan = empirical_plan(tables, syms, plan,
                              chunk_symbols=chunk_symbols,
                              target_escape_prob=target_escape_prob,
                              max_pool_slots_per_1k=64)
        entries[name] = registry.register_tables(name, tables, plan,
                                                 counts=counts)
    return entries


def _layer_index(key) -> int:
    if isinstance(key, int):
        return key
    s = str(key)
    return int(s[1:] if s.startswith("l") else s)


def calibrate_kv_entries(registry, layer_arrays, *, mode: str = "qlc",
                         chunk_symbols: int = 1024,
                         target_escape_prob: float = 1e-4,
                         prefix: str = "kv",
                         plane_split_min_symbols: Optional[int] = None,
                         merge_tol: float = 0.05,
                         allow_search: bool = False) -> Dict[str, "object"]:
    """Calibrate per-layer KV/SSM-state codecs into ``registry``.

    ``layer_arrays`` maps layer keys (``"l0"``/``0``/...) to the state
    arrays that layer's cache blocks will carry (attention K/V slices,
    SSM state leaves) — e.g. a prefill-state snapshot. In ``"e4m3"``
    mode each layer's e4m3-symbol histogram registers one codec under
    ``f"{prefix}/layer{i}"``; in the lossless ``"qlc"`` mode each
    **byte plane** (:func:`byte_planes`) registers its own codec under
    ``f"{prefix}/layer{i}/w{itemsize}b{j}"`` — planes are where the
    byte stream is stationary, so per-plane LUTs + slot capacities win
    where one interleaved codec cannot. Layers whose planes are smaller
    than ``plane_split_min_symbols`` (default ``2 * chunk_symbols``)
    register ONE interleaved codec under the base name instead —
    per-plane container framing would eat the win on tiny states. The
    chosen layout is recorded by which names exist, so the paged cache
    derives it from the registry, never re-guessing from block sizes.

    **Cross-layer LUT sharing** (``merge_tol``): the same byte plane of
    different layers (e.g. every K exponent byte) has nearly the same
    histogram, and registering per-layer tables for each would blow up
    the scheme-id space linearly in depth for no coding gain. New
    streams whose normalized histograms are within total-variation
    distance ``merge_tol`` of a group's first member share ONE set of
    tables built from the group's summed counts — the registry's table
    digest then collapses the whole group onto one scheme-id (one LUT
    on device). Slot capacity stays **per name**: each stream's plan is
    empirically sized from its own measured chunk sums
    (:func:`empirical_plan`), so sharing tables never inflates another
    layer's containers. ``merge_tol=0`` disables merging (only
    bit-identical tables dedupe, the pre-sharing behavior).

    Returns ``{name: CodecEntry}``.
    """
    if plane_split_min_symbols is None:
        plane_split_min_symbols = 2 * chunk_symbols

    # Pass 1: collect every (name, symbol stream) needing registration,
    # in deterministic layer order.
    pending = []                      # [(name, syms)]
    layout: list = []                 # names in output order
    for key in sorted(layer_arrays, key=_layer_index):
        base = f"{prefix}/layer{_layer_index(key)}"
        if mode == "e4m3":
            streams = [(base, kv_symbol_stream(layer_arrays[key], mode))]
        else:
            planes = byte_planes(layer_arrays[key])
            if min((p.size for p in planes.values()), default=0) \
                    >= plane_split_min_symbols:
                streams = [(f"{base}/w{isz}b{j}", plane)
                           for (isz, j), plane in planes.items()]
            else:
                streams = [(base,
                            kv_symbol_stream(layer_arrays[key], "qlc"))]
        for name, syms in streams:
            layout.append(name)
            if name not in registry:
                pending.append((name, np.asarray(syms)))

    # Pass 2: group pending streams by histogram similarity; one set of
    # tables per group (summed counts), one empirically-sized plan per
    # stream.
    groups = []   # [{pmf, counts, members: [(name, syms, counts)]}]
    for name, syms in pending:
        counts = np.maximum(
            np.bincount(syms, minlength=256).astype(np.float64), 1e-6)
        pmf = counts / counts.sum()
        for g in groups:
            if merge_tol > 0 and \
                    0.5 * float(np.abs(pmf - g["pmf"]).sum()) <= merge_tol:
                g["counts"] += counts
                g["members"].append((name, syms, counts))
                break
        else:
            groups.append({"pmf": pmf, "counts": counts.copy(),
                           "members": [(name, syms, counts)]})

    entries = {}
    for g in groups:
        tables = adapt.calibrate_tables(g["counts"],
                                        allow_search=allow_search)
        for name, syms, counts in g["members"]:
            plan = plan_for_tables(tables, counts,
                                   chunk_symbols=chunk_symbols,
                                   target_escape_prob=target_escape_prob)
            # Capped pool: the paged cache wires incompressible streams
            # raw (codec_wins), so the pool never needs to cover a
            # pathological escape rate here.
            plan = empirical_plan(tables, syms, plan,
                                  chunk_symbols=chunk_symbols,
                                  target_escape_prob=target_escape_prob,
                                  max_pool_slots_per_1k=64)
            entries[name] = registry.register_tables(name, tables, plan,
                                                     counts=counts)
    return {name: entries.get(name, registry[name]) for name in layout}
