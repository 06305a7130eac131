"""Training launcher.

The per-host entry point: (jax distributed init ->) a mesh over the
devices present (``launch.mesh.make_device_mesh``) -> trainer. On CPU
it runs reduced configs for verification. The dry-run
(``repro.launch.dryrun``) is the compile-only counterpart for the
full-size cells.

Examples:
  python -m repro.launch.train --arch deepseek-moe-16b --reduced \\
      --steps 50 --comm qlc
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax
import jax.numpy as jnp

from repro.comm import calibrate_for_gradients
from repro.comm.calibrate import calibrate_moe_entries, histogram_of_tree
from repro.comm.channel import Channel, ChannelSpec
from repro.configs import get_config, reduced as make_reduced
from repro.core import CodecRegistry
from repro.data import DataConfig, SyntheticDataset
from repro.launch.mesh import make_device_mesh
from repro.models import init_params
from repro.parallel import sharding as shd
from repro.runtime import enable_compile_cache
from repro.training import (OptConfig, Trainer, TrainerConfig, TrainConfig,
                            init_compressed_opt_state, make_baseline_step,
                            make_compressed_step)
from repro.training import optimizer as optm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="small same-family config (CPU verification)")
    ap.add_argument("--pods", type=int, default=1,
                    help="leading 'pod' (DCN-tier) mesh axis size. With "
                         "--pods N > 1 the compressed gradient wire "
                         "runs ONE pod-bound collective per phase over "
                         "the combined pod x data group (hierarchical "
                         "transport: intra-pod ring + one compressed "
                         "inter-pod bridge per hop group) instead of "
                         "the sequential per-axis collectives. On CPU, "
                         "simulate hosts with "
                         "XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--comm", default="baseline",
                    choices=["baseline", "qlc"])
    ap.add_argument("--transport", default="auto",
                    choices=["auto", "oneshot", "ring", "hierarchical"],
                    help="compressed-collective transport: 'auto' lets "
                         "the planner's per-link-class alpha-beta model "
                         "pick one-shot vs ring/hierarchical (+ hop "
                         "chunking) per collective/axis; 'hierarchical' "
                         "(with --pods > 1) forces the intra-pod ring + "
                         "inter-pod bridge schedule")
    ap.add_argument("--moe-wire", default="auto",
                    choices=["auto", "qlc", "raw"],
                    help="expert all_to_all wire for shardmap_a2a MoE "
                         "configs: 'qlc' calibrates moe/dispatch + "
                         "moe/combine codecs from the first batch's "
                         "routed traffic and sends QLC containers over "
                         "the expert axis; 'raw' sends uncompressed "
                         "activations; 'auto' follows --comm")
    ap.add_argument("--moe-transport", default="auto",
                    choices=["auto", "oneshot", "ring"],
                    help="a2a transport for the compressed MoE wire "
                         "('auto' = planner's distance-charged ring "
                         "vs one-shot choice per payload)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure this host's decode throughput and "
                         "autotune the per-axis transport "
                         "(Channel.autotune); tunings are cached in the "
                         "codec registry and picked up by --transport "
                         "auto")
    ap.add_argument("--adapt", action="store_true",
                    help="online codec adaptation (--comm qlc): the "
                         "step emits fused encode-pass histograms, a "
                         "drift policy watches measured vs planned "
                         "bits/symbol, and a drifted codec is "
                         "recalibrated + hot-swapped under a new "
                         "scheme-id (repro.adaptive)")
    ap.add_argument("--adapt-every", type=int, default=10,
                    help="steps between drift checks with --adapt")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (cluster)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    if args.distributed:
        jax.distributed.initialize()

    if args.pods < 1:
        raise SystemExit(f"--pods must be >= 1, got {args.pods}")
    if args.transport == "hierarchical" and args.pods == 1:
        raise SystemExit(
            "--transport hierarchical needs --pods > 1 (a pod axis to "
            "bridge); with one pod it would just be the ring")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    mesh = make_device_mesh(pods=args.pods)
    if args.moe_wire == "qlc" and cfg.moe is not None:
        # an explicit compressed expert wire implies real expert-
        # parallel dispatch (the other impls never touch the wire)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl="shardmap_a2a"))

    seq = args.seq_len or (128 if args.reduced else 4096)
    batch = args.global_batch or (8 if args.reduced else 256)

    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(10, args.steps // 20))
    train_cfg = TrainConfig(
        microbatches=args.microbatches,
        batch_axes=tuple(a for a in ("pod", "data")
                         if a in mesh.axis_names))
    data = SyntheticDataset(
        DataConfig(vocab_size=cfg.vocab_size,
                   seq_len=seq - cfg.frontend_prefix_len,
                   global_batch=batch),
        host_index=jax.process_index(), host_count=jax.process_count())

    with shd.use_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        b0 = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

        # Expert-parallel MoE wire: one calibrated codec + Channel per
        # a2a direction, bound on the expert ("model") axis.
        moe_channels = None
        moe_wire = args.moe_wire
        if moe_wire == "auto":
            moe_wire = "qlc" if args.comm == "qlc" else "raw"
        if (moe_wire == "qlc" and cfg.moe is not None
                and cfg.moe.impl == "shardmap_a2a"
                and "model" in mesh.axis_names):
            moe_registry = CodecRegistry()
            calibrate_moe_entries(moe_registry, cfg, params, b0)
            dm = int(mesh.shape["model"])
            moe_channels = {}
            for name in ("moe/dispatch", "moe/combine"):
                moe_channels[name] = Channel(
                    ChannelSpec(codec=name, transport=args.moe_transport,
                                axis="model", axis_size=dm),
                    registry=moe_registry)
                logging.info(
                    "moe codec %s: scheme-id %s, %.2f bits/sym", name,
                    moe_registry[name].scheme_id,
                    moe_registry[name].plan.expected_bits_per_symbol)

        baseline = jax.jit(make_baseline_step(
            cfg, opt_cfg, train_cfg, moe_channels=moe_channels))
        on_step = None
        if args.comm == "qlc":
            # per-tensor-type registry: the gradient reduce-scatter and
            # the parameter all-gather get separately calibrated codecs
            tables, plan = calibrate_for_gradients(cfg, params, b0)
            registry = CodecRegistry()
            registry.register_tables("grads", tables, plan)
            registry.register("params", histogram_of_tree(params),
                              chunk_symbols=plan.chunk_symbols)
            hierarchical = args.pods > 1 and "pod" in mesh.axis_names
            if args.autotune:
                _autotune_transports(registry, cfg, mesh, train_cfg,
                                     hierarchical=hierarchical)

            def build_step():
                return jax.jit(make_compressed_step(
                    cfg, opt_cfg, train_cfg, mesh, registry,
                    transport=args.transport,
                    hierarchical_wire=hierarchical,
                    moe_channels=moe_channels,
                    telemetry=args.adapt))

            step = build_step()
            opt_state = init_compressed_opt_state(
                cfg, mesh, train_cfg, registry, opt_cfg)
            if args.adapt:
                from repro.adaptive import (AdaptiveController,
                                            TrainingAdapter)
                controller = AdaptiveController(registry)
                on_step = TrainingAdapter(
                    controller, build_step,
                    grad_key="grads", param_key="params",
                    check_every=args.adapt_every,
                    on_swap=lambda ev: logging.info(
                        "codec hot-swap %s: scheme-id %d -> %d "
                        "(%.2f measured vs %.2f planned bits/sym; "
                        "new plan %.2f)", ev.name, ev.old_scheme_id,
                        ev.new_scheme_id, ev.measured_bits,
                        ev.old_expected_bits, ev.new_expected_bits))
        else:
            step = baseline
            opt_state = optm.init_state(params, opt_cfg)

        trainer = Trainer(
            TrainerConfig(total_steps=args.steps,
                          checkpoint_dir=args.checkpoint_dir),
            step, fallback_step_fn=None, on_step=on_step)
        params, opt_state, start = trainer.restore_or(params, opt_state)
        trainer.run(params, opt_state, data, start_step=start)

    losses = [h["loss"] for h in trainer.history]
    print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


def _autotune_transports(registry, model_cfg, mesh, train_cfg,
                         hierarchical: bool = False):
    """Autotune the step's per-axis transports into the registry.

    Builds one ``transport="auto"`` channel per (tensor type, dp axis)
    — the same binding ``make_compressed_step`` opens — and runs
    ``Channel.autotune`` at the flat-gradient payload each axis
    actually moves, probing each axis's WIRE bandwidth on the real mesh
    (``mesh=`` — one timed ppermute per axis, cached per link class in
    the registry) alongside decode throughput. The tuned
    ``TransportConfig``s land in the registry's cache, which the
    step's auto channels consult first.

    ``hierarchical=True`` mirrors the ``--pods`` wire: one POD-BOUND
    channel per tensor type over the combined pod x data group (the
    wire probe then measures both the ICI "data" hop and the DCN "pod"
    bridge) instead of per-axis flat channels.
    """
    from repro.comm.channel import Channel, ChannelSpec
    from repro.training.train_step import dp_axes_in, flat_geometry
    dp_axes = dp_axes_in(mesh, train_cfg)
    _, n_padded, _, _ = flat_geometry(
        model_cfg, mesh, train_cfg, registry["grads"].config())
    n = n_padded
    if hierarchical and "pod" in dp_axes and "data" in dp_axes:
        ld, pd = int(mesh.shape["data"]), int(mesh.shape["pod"])
        for name, is_reduce in (("grads", True), ("params", False)):
            ch = Channel(ChannelSpec(codec=name, transport="auto",
                                     axis="data", axis_size=ld,
                                     pod_axis="pod", pod_axis_size=pd),
                         registry=registry)
            tuned = ch.autotune(4 * (n // (ld * pd)),
                                is_reduce=is_reduce, mesh=mesh)
            logging.info("autotuned %s over pod x data (%d x %d): %s",
                         name, pd, ld, tuned.transport)
        return
    for ax in (a for a in ("data", "pod") if a in dp_axes):
        d = int(mesh.shape[ax])
        # grads feed the reduce-scatter (charged its per-rank
        # accumulate dispatches), params the all-gather
        for name, is_reduce in (("grads", True), ("params", False)):
            ch = Channel(ChannelSpec(codec=name, transport="auto",
                                     axis=ax, axis_size=d),
                         registry=registry)
            tuned = ch.autotune(4 * (n // d), is_reduce=is_reduce,
                                mesh=mesh,
                                axis_link="dcn" if ax == "pod" else "ici")
            logging.info("autotuned %s over %s (d=%d): %s",
                         name, ax, d, tuned.transport)
        n //= d


if __name__ == "__main__":
    main()
