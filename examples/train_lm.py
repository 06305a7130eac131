"""End-to-end training driver: LM training with QLC-compressed gradient
collectives, checkpointing, and fault-tolerant step retry.

Defaults run a small model for a quick CPU demo; --preset 100m trains a
~100M-param model for a few hundred steps (same code path — expect
hours on CPU, minutes on real accelerators).

Multi-device (recommended, exercises the real compressed collectives):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/train_lm.py --comm qlc --steps 50

Run:  PYTHONPATH=src python examples/train_lm.py --steps 30
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.comm import calibrate_for_gradients
from repro.comm.calibrate import calibrate_moe_entries, histogram_of_tree
from repro.comm.channel import Channel, ChannelSpec
from repro.configs import get_config, reduced
from repro.core import CodecRegistry
from repro.models import moe as moe_mod
from repro.data import DataConfig, SyntheticDataset
from repro.launch.mesh import make_device_mesh
from repro.models import init_params
from repro.parallel import sharding as shd
from repro.training import (OptConfig, Trainer, TrainerConfig, TrainConfig,
                            init_compressed_opt_state, make_baseline_step,
                            make_compressed_step, step_channels)
from repro.training import optimizer as optm


def build_cfg(preset: str):
    base = get_config("gemma-2b-sft")   # the paper's own model family
    if preset == "tiny":
        return reduced(base, d_model=128, num_layers=4, num_heads=4,
                       num_kv_heads=1, d_ff=512, vocab_size=512)
    if preset == "100m":
        return dataclasses.replace(
            base, name="gemma-100m", num_layers=8, d_model=768,
            num_heads=8, num_kv_heads=1, head_dim=96, d_ff=3072,
            vocab_size=32768, remat="none")
    if preset == "moe":
        # expert-parallel MoE over the compressed a2a expert wire
        cfg = reduced(get_config("deepseek-moe-16b"))
        return dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl="shardmap_a2a"))
    raise ValueError(preset)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "100m", "moe"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--comm", default="qlc", choices=["baseline", "qlc"])
    ap.add_argument("--transport", default="auto",
                    choices=["auto", "oneshot", "ring"],
                    help="wire transport policy bound into the step's "
                         "channels (auto = per-payload planner choice)")
    ap.add_argument("--adapt", action="store_true",
                    help="online codec adaptation (with --comm qlc): "
                         "the step emits fused encode histograms; a "
                         "drifted codec is recalibrated off the hot "
                         "path and hot-swapped under a new scheme-id")
    ap.add_argument("--adapt-every", type=int, default=5,
                    help="steps between drift checks with --adapt")
    ap.add_argument("--pool-slots", type=int, default=None,
                    help="escape-pool slots per 1k symbols for the "
                         "grad/param codecs (reduced smoke models have "
                         "few chunks per rank, so the planner's ~1-slot "
                         "pool can overflow into per-step fallback; "
                         "1024 makes the wire unconditionally lossless)")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = build_cfg(args.preset)
    mesh = make_device_mesh(model=2 if len(jax.devices()) > 1 else 1)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"model: {cfg.name} params~{cfg.param_count()/1e6:.1f}M")

    opt_cfg = OptConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    train_cfg = TrainConfig(microbatches=1, batch_axes=("data",))
    data = SyntheticDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=0))

    with shd.use_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch0 = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

        # MoE expert wire: calibrate the dispatch/combine codecs from
        # the actual routed-token traffic of batch0 and bind one
        # Channel per direction on the expert ("model") axis — the
        # step's forward routes every expert all_to_all through them.
        moe_channels = None
        if (cfg.moe is not None and cfg.moe.impl == "shardmap_a2a"
                and "model" in mesh.axis_names):
            moe_registry = CodecRegistry()
            calibrate_moe_entries(moe_registry, cfg, params, batch0)
            dm = int(mesh.shape["model"])
            geo = moe_mod.shardmap_a2a_geometry(
                cfg, args.batch * args.seq_len, mesh)
            moe_channels = {}
            for name in (moe_mod.MOE_DISPATCH, moe_mod.MOE_COMBINE):
                ch = Channel(ChannelSpec(codec=name,
                                         transport=args.transport,
                                         axis="model", axis_size=dm),
                             registry=moe_registry)
                moe_channels[name] = ch
                entry = moe_registry[name]
                wire = ch.modeled_wire_bytes(geo["row_values"])
                print(f"moe codec {name}: scheme-id {entry.scheme_id}, "
                      f"{entry.plan.expected_bits_per_symbol:.2f} "
                      f"bits/sym, "
                      f"{dm * wire / geo['ng']:.0f} wire B/token "
                      f"per collective")

        baseline = jax.jit(make_baseline_step(cfg, opt_cfg, train_cfg,
                                              moe_channels=moe_channels))
        on_step = None
        if args.comm == "qlc":
            # Per-tensor-type registry (paper §7): one codec for the
            # gradient reduce-scatter, one for the updated-parameter
            # all-gather — the two collectives see very different
            # symbol statistics.
            tables, plan = calibrate_for_gradients(
                cfg, params, batch0, chunk_symbols=512)
            if args.pool_slots is not None:
                plan = dataclasses.replace(
                    plan, pool_slots_per_1k=args.pool_slots)
            registry = CodecRegistry()
            registry.register_tables("grads", tables, plan)
            registry.register("params", histogram_of_tree(params),
                              chunk_symbols=512,
                              pool_slots_per_1k=args.pool_slots or 8)
            for name in ("grads", "params"):
                e = registry[name]
                print(f"calibrated {name}: scheme-id {e.scheme_id}, "
                      f"{e.plan.expected_bits_per_symbol:.2f} bits/sym, "
                      f"slot {e.plan.capacity_words * 32 / 512:.2f}")
            comm_cfg = registry["grads"].config()
            # The step binds codec x transport x axis ONCE per
            # (collective, dp axis) as Channel objects — inspect the
            # same binding it will open:
            rs_ch, _ag_ch, _cfg = step_channels(
                registry, dp_sizes={a: mesh.shape[a]
                                    for a in train_cfg.batch_axes
                                    if a in mesh.axis_names},
                rs_order=tuple(a for a in ("data", "pod")
                               if a in mesh.axis_names),
                transport=args.transport)
            for ax, ch in rs_ch.items():
                print(f"grad RS channel over {ax!r}: {ch}")
            def build_step():
                return jax.jit(make_compressed_step(
                    cfg, opt_cfg, train_cfg, mesh, registry,
                    transport=args.transport,
                    moe_channels=moe_channels, telemetry=args.adapt))

            step = build_step()
            opt_state = init_compressed_opt_state(
                cfg, mesh, train_cfg, registry, opt_cfg)
            fallback = baseline_adapter(baseline, cfg, mesh, train_cfg,
                                        comm_cfg, opt_cfg)
            if args.adapt:
                # Telemetry -> drift policy -> hot-swap: the step's
                # adapt/*_hist metrics feed the controller; a swap
                # registers a NEW scheme-id (old entries stay
                # decodable) and the adapter rebuilds the jitted step
                # against the updated registry.
                from repro.adaptive import (AdaptiveController,
                                            TrainingAdapter)
                controller = AdaptiveController(registry)
                on_step = TrainingAdapter(
                    controller, build_step,
                    grad_key="grads", param_key="params",
                    check_every=args.adapt_every,
                    on_swap=lambda ev: print(
                        f"hot-swap {ev.name}: scheme-id "
                        f"{ev.old_scheme_id} -> {ev.new_scheme_id} "
                        f"({ev.measured_bits:.2f} measured vs "
                        f"{ev.old_expected_bits:.2f} planned bits/sym)"))
        else:
            step = baseline
            opt_state = optm.init_state(params, opt_cfg)
            fallback = None

        trainer = Trainer(
            TrainerConfig(total_steps=args.steps,
                          checkpoint_dir=args.checkpoint_dir,
                          checkpoint_every=max(10, args.steps // 3),
                          log_every=5),
            step, fallback_step_fn=fallback, on_step=on_step)
        params, opt_state, start = trainer.restore_or(params, opt_state)
        params, opt_state = trainer.run(params, opt_state, data,
                                        start_step=start)

    losses = [h["loss"] for h in trainer.history]
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f} over "
          f"{len(losses)} steps (fallbacks: {trainer.comm_fallbacks})")
    assert losses[-1] < losses[0], "training did not reduce the loss"
    print("OK")


def baseline_adapter(baseline, cfg, mesh, train_cfg, comm_cfg, opt_cfg):
    """Comm-failure fallback: rerun the step uncompressed. The ZeRO-1
    flat opt state stays authoritative; the fallback recomputes grads
    and applies the same update through the raw-e4m3 wire (enabled=False
    => identical numerics to a lossless compressed step)."""
    import dataclasses as dc
    from repro.comm import calibrate_for_gradients  # noqa: F401
    from repro.core import TABLE1, build_tables, distributions
    tables = build_tables(distributions.grad_counts(1 << 16), TABLE1)
    raw_cfg = dc.replace(comm_cfg, enabled=False)
    from repro.training import make_compressed_step as mk
    return jax.jit(mk(cfg, opt_cfg, train_cfg, mesh, tables, raw_cfg))


if __name__ == "__main__":
    main()
