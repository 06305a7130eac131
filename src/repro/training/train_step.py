"""Train steps.

Two interchangeable implementations:

* **baseline** — GSPMD end to end: FSDP+TP sharding rules, XLA inserts
  all collectives (bf16/f32 wire). This is the roofline baseline and the
  path that runs every dry-run cell.

* **compressed** — the paper's technique integrated into training.
  Stage 1 computes per-data-shard gradients under ``jax.shard_map`` with
  only the dp axes manual (the model axis stays under GSPMD). Stage 2 is
  a fully-manual shard_map that flattens each rank's local gradient
  shard and performs a **hierarchical QLC-compressed reduce-scatter**
  (intra-pod over "data", then cross-pod over "pod" — the cross-pod hop,
  the scarcest bandwidth, moves 1/d_data of the data after the intra-pod
  RS), a ZeRO-1 sharded AdamW update on the owned slice, and the
  mirrored compressed all-gathers back. Gradient bytes on the wire
  shrink ~2.1x vs bf16 (e4m3 + QLC at the planner's capacity).

  The wire is lossless relative to the e4m3-quantized values; if the
  escape pool ever overflows (``ok=False`` in metrics) the trainer
  retries the step through the baseline path — numerics never silently
  corrupt.

Parameters in compressed mode are dp-replicated (TP-sharded only);
archs too large for that (nemotron-340b, jamba-398b at full size) train
via the baseline FSDP path (see DESIGN.md §8).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import CommConfig
from repro.comm.channel import Channel, ChannelSpec
from repro.configs.base import ModelConfig
from repro.core.registry import CodecRegistry
from repro.models import init_params, next_token_loss, param_specs
from repro.parallel import sharding as shd
from repro.training import optimizer as opt

GRAD_TYPE = "grads"      # registry key for the gradient reduce-scatter
PARAM_TYPE = "params"    # registry key for the parameter all-gather


def step_channels(codec, comm_cfg: CommConfig = None, *,
                  dp_sizes, rs_order, transport=None, transport_model=None,
                  pod_axis=None,
                  grad_key: str = GRAD_TYPE, param_key: str = PARAM_TYPE):
    """Open the compressed step's wire channels: one per (collective,
    dp axis) — the single point where codec x transport x axis is bound
    (this replaced the old ``resolve_step_codecs`` /
    ``resolve_step_transports`` / ``_auto_axis_transports`` trio).

    ``codec`` is either a bare ``CodecTables`` (legacy: one LUT + one
    ``comm_cfg`` for both collectives) or a ``CodecRegistry`` holding a
    ``grad_key`` entry (gradient reduce-scatter wire) and optionally a
    ``param_key`` entry (updated-parameter all-gather wire; falls back
    to the grad entry). With a registry, ``comm_cfg`` acts as an
    override source for the non-plan knobs (``enabled``,
    ``use_kernels``, ``scale_dtype``) on top of each entry's calibrated
    plan.

    ``transport`` is ``None`` (one-shot everywhere, legacy), a
    ``TransportConfig``/str applied to both collectives, ``"auto"``
    (each channel resolves one-shot vs ring + hop chunking per call
    from the static payload geometry — registry-cached autotunings
    first, then the planner's alpha-beta model, with the one-shot RS
    charged its per-rank accumulate dispatches), or a dict with
    ``grad_key``/``param_key`` entries — per-collective transport
    policies next to the per-collective codec keys.

    ``pod_axis`` (with its size present in ``dp_sizes``) binds every
    opened channel to that slow second axis: each collective then runs
    once over the combined pod x local group (``rs_order`` should name
    only the local axis), and ``"hierarchical"``/``"auto"`` transports
    ring within the pod while bridging pods with one compressed
    exchange per hop group — the multi-host wire.

    Returns ``(rs_channels, ag_channels, rs_cfg)``: ``{axis: Channel}``
    maps over ``rs_order``, plus the gradient wire's resolved
    ``CommConfig`` (the step's flat-vector geometry is derived from
    it).
    """
    if isinstance(transport, dict):
        rs_t = transport.get(grad_key)
        ag_t = transport.get(param_key)
    else:
        rs_t = ag_t = transport

    registry = codec if isinstance(codec, CodecRegistry) else None
    if registry is not None:
        g = registry.get(grad_key)
        if g is None:
            raise KeyError(
                f"registry has no {grad_key!r} entry; have "
                f"{registry.names()}")
        p = registry.get(param_key, default=g)
        overrides = {}
        if comm_cfg is not None:
            overrides = dict(enabled=comm_cfg.enabled,
                             use_kernels=comm_cfg.use_kernels,
                             scale_dtype=comm_cfg.scale_dtype)
        rs_codec, ag_codec = g, p
        rs_cfg, ag_cfg = g.config(**overrides), p.config(**overrides)
    else:
        if comm_cfg is None:
            raise TypeError("bare CodecTables needs an explicit CommConfig")
        rs_codec = ag_codec = codec
        rs_cfg = ag_cfg = comm_cfg
    if rs_cfg.chunk_symbols != ag_cfg.chunk_symbols:
        raise ValueError(
            "grad and param codecs must share chunk_symbols, got "
            f"{rs_cfg.chunk_symbols} vs {ag_cfg.chunk_symbols}")

    def open_axis(codec_, cfg_, t, ax):
        pod_kw = {}
        if pod_axis is not None and ax != pod_axis:
            pod_kw = dict(pod_axis=pod_axis,
                          pod_axis_size=int(dp_sizes[pod_axis]))
        return Channel(
            ChannelSpec(codec=codec_, cfg=cfg_, transport=t, axis=ax,
                        axis_size=int(dp_sizes[ax]), **pod_kw),
            registry=registry, model=transport_model)

    rs_ch = {ax: open_axis(rs_codec, rs_cfg, rs_t, ax) for ax in rs_order}
    ag_ch = {ax: open_axis(ag_codec, ag_cfg, ag_t, ax) for ax in rs_order}
    return rs_ch, ag_ch, rs_cfg


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    comm_mode: str = "baseline"      # baseline | compressed
    batch_axes: Tuple[str, ...] = ("pod", "data")


def dp_axes_in(mesh: Mesh, cfg: TrainConfig) -> Tuple[str, ...]:
    return tuple(a for a in cfg.batch_axes if a in mesh.axis_names)


def dp_size_of(mesh: Mesh, cfg: TrainConfig) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes_in(mesh, cfg)],
                       initial=1))


def batch_pspec(mesh: Mesh, cfg: TrainConfig) -> P:
    axes = dp_axes_in(mesh, cfg)
    return P(axes if axes else None)


def _loss_fn(model_cfg: ModelConfig, moe_channels=None):
    """Loss closure; ``moe_channels`` (a ``{name: Channel}`` map over
    ``moe.MOE_DISPATCH``/``moe.MOE_COMBINE``) puts the expert-parallel
    ``shardmap_a2a`` dispatch on the compressed wire — the binding is
    consulted when the loss is TRACED, so it wraps the call here."""
    from repro.models import moe as moe_mod

    def f(params, batch):
        ctx = (moe_mod.bind_moe_channels(moe_channels)
               if moe_channels else contextlib.nullcontext())
        with ctx:
            return next_token_loss(
                params, model_cfg, batch["tokens"], batch["labels"],
                batch.get("prefix_emb"))
    return f


def _microbatched_grads(loss_fn, params, batch, n_micro: int):
    """Gradient accumulation over n_micro microbatches (scan)."""
    if n_micro == 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    split = jax.tree.map(
        lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
        batch)

    def body(carry, mb):
        acc, loss_acc = carry
        l, g = jax.value_and_grad(loss_fn)(params, mb)
        acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, g)
        return (acc, loss_acc + l), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gacc, lacc), _ = jax.lax.scan(body, (zeros, jnp.float32(0)), split)
    inv = 1.0 / n_micro
    return lacc * inv, jax.tree.map(lambda g: g * inv, gacc)


# --------------------------------------------------------------------------
# Baseline (GSPMD) step
# --------------------------------------------------------------------------

def make_baseline_step(model_cfg: ModelConfig, opt_cfg: opt.OptConfig,
                       train_cfg: TrainConfig, *,
                       moe_channels=None) -> Callable:
    """``moe_channels`` compresses the MoE expert all_to_all (forward
    activations) even in baseline comm mode — the gradient wire stays
    dense while ``moe.impl="shardmap_a2a"`` moves QLC containers."""
    loss_fn = _loss_fn(model_cfg, moe_channels=moe_channels)

    def train_step(params, opt_state, batch):
        loss, grads = _microbatched_grads(
            loss_fn, params, batch, train_cfg.microbatches)
        new_params, new_state, info = opt.apply_update(
            params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, "ok": jnp.bool_(True), **info}
        return new_params, new_state, metrics

    return train_step


# --------------------------------------------------------------------------
# Compressed-communication step
# --------------------------------------------------------------------------

def _manual_param_specs(model_cfg: ModelConfig, mesh: Mesh):
    """PartitionSpecs for params under manual model sharding
    (dp-replicated), with shape-aware divisibility fallback."""
    shapes = jax.eval_shape(
        lambda k: init_params(model_cfg, k), jax.random.PRNGKey(0))
    specs = param_specs(model_cfg)
    with shd.use_mesh(mesh):
        rules = shd.get_rules()
        pspecs = jax.tree.map(
            lambda spec, leaf: rules.spec(spec, shape=leaf.shape),
            specs, shapes, is_leaf=shd.is_spec_leaf)
    return pspecs, shapes


def _local_numel(pspec: P, shape, mesh: Mesh) -> int:
    n = 1
    entries = tuple(pspec) + (None,) * (len(shape) - len(pspec))
    for dim, entry in zip(shape, entries):
        if entry is None:
            n *= dim
        else:
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            n *= dim // int(np.prod([mesh.shape[a] for a in axes]))
    return n


def _replication_factor(pspec: P, mesh: Mesh,
                        model_axes=("model",)) -> float:
    used = set()
    for entry in tuple(pspec):
        if entry is None:
            continue
        for a in ((entry,) if isinstance(entry, str) else entry):
            used.add(a)
    rep = 1
    for a in model_axes:
        if a in mesh.axis_names and a not in used:
            rep *= mesh.shape[a]
    return float(rep)


def flat_geometry(model_cfg: ModelConfig, mesh: Mesh,
                  train_cfg: TrainConfig, comm_cfg: CommConfig):
    """(n_local, n_padded, seg, (leaf_starts, leaf_weights)) of the
    per-model-rank flat parameter vector. Position ``i`` of the flat
    vector has weight ``leaf_weights[j]`` for the last ``leaf_starts[j]
    <= i``: model-replicated leaves are downweighted so the psum'd grad
    norm is exact, and the padding tail weighs 0."""
    pspecs, shapes = _manual_param_specs(model_cfg, mesh)
    dp_total = dp_size_of(mesh, train_cfg)
    k = comm_cfg.chunk_symbols

    leaves_spec = jax.tree.leaves(pspecs,
                                  is_leaf=lambda s: isinstance(s, P))
    leaves_shape = jax.tree.leaves(shapes)
    sizes = [_local_numel(s, l.shape, mesh)
             for s, l in zip(leaves_spec, leaves_shape)]
    reps = [_replication_factor(s, mesh)
            for s, l in zip(leaves_spec, leaves_shape)]
    n_local = int(sum(sizes))
    n_padded = -(-n_local // (dp_total * k)) * (dp_total * k)
    seg = n_padded // dp_total
    starts = np.cumsum([0] + sizes).astype(np.int32)
    weights = np.array([1.0 / r for r in reps] + [0.0], np.float32)
    return n_local, n_padded, seg, (starts, weights)


def _flatten_local(tree) -> Tuple[jnp.ndarray, Any]:
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    meta = (treedef, [(l.shape, l.dtype) for l in leaves])
    return flat, meta


def _pad_with_head(flat: jnp.ndarray, n: int) -> jnp.ndarray:
    """``flat`` padded to length ``n`` with copies of its head, so the
    padding codes like the payload: a run of zeros can code past the
    chunk slot (zero is a rare, long symbol among weights) and overflow
    the escape pool. The padding is discarded after the all-gather."""
    pad = n - flat.shape[0]
    if pad == 0:
        return flat
    head = flat[:pad]
    reps = -(-pad // head.shape[0])
    return jnp.concatenate([flat, jnp.tile(head, reps)[:pad]])


def _unflatten_local(flat: jnp.ndarray, meta) -> Any:
    treedef, shapes = meta
    out, off = [], 0
    for shape, dtype in shapes:
        n = int(np.prod(shape, initial=1))
        out.append(flat[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def make_compressed_step(model_cfg: ModelConfig, opt_cfg: opt.OptConfig,
                         train_cfg: TrainConfig, mesh: Mesh,
                         tables, comm_cfg: CommConfig = None, *,
                         grad_key: str = GRAD_TYPE,
                         param_key: str = PARAM_TYPE,
                         transport=None,
                         transport_model=None,
                         hierarchical_wire: bool = False,
                         moe_channels=None,
                         telemetry: bool = False) -> Callable:
    """train_step(params, flat_opt_state, batch) for compressed mode.

    ``telemetry=True`` additionally returns the encode-side symbol
    histograms of the gradient and parameter wires in the metrics
    (``"adapt/grads_hist"`` / ``"adapt/params_hist"``, i32[256],
    psum'd over every rank — global traffic). The histogram rides the
    fused encode kernel (``emit_hist``), so the payload math is
    untouched: a telemetry step is bit-identical to a plain one. These
    are the ``repro.adaptive.TrainingAdapter`` inputs.

    ``tables`` is a legacy ``CodecTables`` (with ``comm_cfg``) or a
    ``CodecRegistry``: the gradient reduce-scatter then uses the
    ``grad_key`` codec and the parameter all-gather the ``param_key``
    codec — per-collective tensor-type selection (paper §7).

    ``transport`` selects the collective transport the same way:
    ``None`` (one-shot), a ``TransportConfig``/"ring" for both, a dict
    with ``grad_key``/``param_key`` entries (per-collective transport
    keys), or ``"auto"`` — each channel picks one-shot vs ring (and
    the ring's hop chunking) per dp axis from the static payload
    geometry, preferring transports autotuned into the registry
    (``Channel.autotune``). ``transport_model`` (an
    ``AlphaBetaModel``) supplies measured constants for the ``"auto"``
    choice — e.g. the decode throughput
    ``benchmarks/transport_overlap.py`` measures; default constants
    are the v5e first-order guesses.

    ``hierarchical_wire=True`` (the ``launch/train.py --pods`` path)
    replaces the per-axis sequential collectives on a pod x data mesh
    with ONE pod-bound channel per collective: the reduce-scatter and
    all-gather each run once over the combined group in pod-major rank
    order, and a ``"hierarchical"`` (or ``"auto"``-chosen) transport
    rings within the pod while bridging pods with one compressed
    exchange per hop group. Bit-identical gradients to the one-shot
    combined-group wire; on a mesh without a ``"pod"`` axis the flag
    is a no-op.

    All wire decisions are bound ONCE at step build time as
    :class:`~repro.comm.channel.Channel` objects — one per
    (collective, dp axis) — via :func:`step_channels`.
    """
    loss_fn = _loss_fn(model_cfg, moe_channels=moe_channels)
    dp_axes = dp_axes_in(mesh, train_cfg)
    dp_sizes = {a: mesh.shape[a] for a in dp_axes}
    dp_total = dp_size_of(mesh, train_cfg)
    pod_axis = ("pod" if hierarchical_wire and "pod" in dp_axes
                and "data" in dp_axes else None)
    if pod_axis is not None:
        rs_order = ("data",)            # one pod-bound combined group
    else:
        rs_order = tuple(a for a in ("data", "pod") if a in dp_axes)
    rs_ch, ag_ch, comm_cfg = step_channels(
        tables, comm_cfg, dp_sizes=dp_sizes, rs_order=rs_order,
        transport=transport, transport_model=transport_model,
        pod_axis=pod_axis, grad_key=grad_key, param_key=param_key)

    p_specs, _ = _manual_param_specs(model_cfg, mesh)
    # Stacked-grad specs: stage 1 (model under auto) may only reference
    # the manual dp axes; stage 2 (fully manual) names the model dims.
    g_specs = jax.tree.map(
        lambda s: P(*((dp_axes,) + tuple(s))), p_specs,
        is_leaf=lambda s: isinstance(s, P))
    g_specs_s1 = jax.tree.map(
        lambda s: P(*((dp_axes,) + (None,) * len(tuple(s)))), p_specs,
        is_leaf=lambda s: isinstance(s, P))
    b_spec = batch_pspec(mesh, train_cfg)
    n_local, n_padded, seg_len, (leaf_starts, leaf_weights) = flat_geometry(
        model_cfg, mesh, train_cfg, comm_cfg)

    # ---- stage 1: per-dp-shard gradients (model axis under GSPMD) -------
    # dp axes manual, model axis auto.
    def grad_body(params, batch):
        loss, grads = _microbatched_grads(
            loss_fn, params, batch, train_cfg.microbatches)
        return loss[None], jax.tree.map(lambda g: g[None], grads)

    stage1 = shd.shard_map(
        grad_body, mesh=mesh,
        in_specs=(jax.tree.map(lambda s: P(), p_specs,
                               is_leaf=lambda s: isinstance(s, P)),
                  b_spec),
        out_specs=(P(dp_axes), g_specs_s1),
        manual_axes=dp_axes)

    # ---- stage 2: hierarchical compressed RS + ZeRO-1 Adam + AG ---------
    def sync_body(params, grads_stacked, flat_opt):
        grads_local = jax.tree.map(lambda g: g[0], grads_stacked)
        g_flat, meta = _flatten_local(grads_local)
        p_flat, _ = _flatten_local(params)
        g_flat = _pad_with_head(g_flat, n_padded)
        p_flat = _pad_with_head(p_flat, n_padded)

        seg = g_flat
        ok = jnp.bool_(True)
        ghist = phist = jnp.zeros((256,), jnp.int32)
        for ax in rs_order:     # intra-pod then cross-pod (flat mode),
                                # or ONE pod-bound combined group
            if telemetry:
                (seg, _valid, ok_i), h = rs_ch[ax].reduce_scatter(
                    seg, with_hist=True)
                ghist = ghist + h
            else:
                seg, _valid, ok_i = rs_ch[ax].reduce_scatter(seg)
            ok &= ok_i
        seg = seg / dp_total                    # mean over dp

        # exact global grad norm: weight out model-replication. With a
        # pod-bound wire the segment owner is the pod-major combined
        # rank (the channel's rank convention); flat mode keeps the
        # historic rs_order fold.
        idx = (jax.lax.axis_index(pod_axis).astype(jnp.int32)
               if pod_axis is not None else jnp.int32(0))
        for ax in rs_order:
            idx = idx * dp_sizes[ax] + jax.lax.axis_index(ax)
        # The per-leaf weights by position (a full-length weight vector
        # would be a constant as large as the model).
        pos = idx * seg_len + jnp.arange(seg_len, dtype=jnp.int32)
        w_seg = jnp.asarray(leaf_weights)[
            jnp.searchsorted(jnp.asarray(leaf_starts), pos, side="right") - 1]
        local_sq = jnp.sum(w_seg * jnp.square(seg))
        gnorm = jnp.sqrt(jax.lax.psum(
            local_sq, tuple(dp_axes) + ("model",)))

        p_seg = jax.lax.dynamic_slice(p_flat, (idx * seg_len,), (seg_len,))
        opt_local = {kk: (vv.reshape(vv.shape[-1:]) if vv.ndim else vv)
                     for kk, vv in flat_opt.items()}
        new_seg, new_opt, lr = opt.apply_flat_update(
            p_seg, seg, opt_local, opt_cfg, gnorm)

        full = new_seg
        for ax in reversed(rs_order):   # mirrored: cross-pod first
            if telemetry:
                full, ok_i, h = ag_ch[ax].all_gather(full, with_hist=True)
                phist = phist + h
            else:
                full, ok_i = ag_ch[ax].all_gather(full)
            ok &= ok_i
        # ok is per-rank (each rank decodes different payloads, and the
        # model axis shards the flat vector); the step's retry signal
        # must trip when ANY rank's escape pool overflowed. Reduce it
        # globally — the P() out-spec would otherwise silently report
        # rank 0's flag.
        ok = jnp.equal(jax.lax.psum(
            jnp.where(ok, jnp.int32(0), jnp.int32(1)),
            tuple(dp_axes) + ("model",)), 0)
        new_params = _unflatten_local(full[:n_local], meta)
        new_params = jax.tree.map(lambda a, old: a.astype(old.dtype),
                                  new_params, params)
        new_opt_out = {kk: new_opt[kk].reshape(flat_opt[kk].shape)
                       for kk in flat_opt}
        if telemetry:
            # Global traffic view: every rank encodes a different shard
            # (and the model axis splits the flat vector), so the
            # channel histograms are per-rank. Sum them.
            axes = tuple(dp_axes) + ("model",)
            ghist = jax.lax.psum(ghist, axes)
            phist = jax.lax.psum(phist, axes)
            return (new_params, new_opt_out, ok, gnorm, lr,
                    ghist, phist)
        return new_params, new_opt_out, ok, gnorm, lr

    opt_state_spec = {
        "m": P(*(dp_axes + ("model", None))),
        "v": P(*(dp_axes + ("model", None))),
        "step": P(),
    }

    out_specs = (p_specs, opt_state_spec, P(), P(), P())
    if telemetry:
        out_specs += (P(), P())
    stage2 = shd.shard_map(
        sync_body, mesh=mesh,
        in_specs=(p_specs, g_specs, opt_state_spec),
        out_specs=out_specs)

    def train_step(params, flat_opt_state, batch):
        loss_per_dp, grads_stacked = stage1(params, batch)
        outs = stage2(params, grads_stacked, flat_opt_state)
        new_params, new_opt, ok, gnorm, lr = outs[:5]
        metrics = {"loss": jnp.mean(loss_per_dp), "ok": ok,
                   "grad_norm": gnorm, "lr": lr}
        if telemetry:
            metrics["adapt/grads_hist"] = outs[5]
            metrics["adapt/params_hist"] = outs[6]
        return new_params, new_opt, metrics

    return train_step


def init_compressed_opt_state(model_cfg: ModelConfig, mesh: Mesh,
                              train_cfg: TrainConfig, comm_cfg,
                              opt_cfg: opt.OptConfig):
    """Global ZeRO-1 state arrays [*dp_dims, model, seg].

    ``comm_cfg``: a ``CommConfig``, or the ``CodecRegistry`` passed to
    ``make_compressed_step`` (geometry comes from its grad entry)."""
    if isinstance(comm_cfg, CodecRegistry):
        comm_cfg = Channel(ChannelSpec(codec=GRAD_TYPE),
                           registry=comm_cfg).cfg
    _, _, seg, _ = flat_geometry(model_cfg, mesh, train_cfg, comm_cfg)
    dp_axes = dp_axes_in(mesh, train_cfg)
    lead = tuple(mesh.shape[a] for a in dp_axes) + (mesh.shape["model"],)
    dt = jnp.dtype(opt_cfg.moment_dtype)
    # Each rank's segment is made on its own device (the step's
    # opt_state_spec), never gathered whole on one.
    owned = NamedSharding(mesh, P(*(dp_axes + ("model", None))))
    return {
        "m": jnp.zeros(lead + (seg,), dt, device=owned),
        "v": jnp.zeros(lead + (seg,), dt, device=owned),
        "step": jnp.zeros((), jnp.int32, device=NamedSharding(mesh, P())),
    }
