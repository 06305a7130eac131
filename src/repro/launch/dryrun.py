import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST precede every other import (jax locks the
# platform and device count at first init). 512 placeholder CPU devices
# back the production meshes: 16x16 single pod, 2x16x16 multi-pod. The
# CPU platform is pinned for this process and, through the environment,
# for every --sweep child, so a dry run never takes a host's TPU.

import argparse          # noqa: E402
import gzip              # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ASSIGNED, get_config, shapes_for)  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig       # noqa: E402
from repro.data.synthetic import input_shape_structs          # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.models import (decode_states_specs, decode_step,   # noqa: E402
                          init_decode_states, init_params,
                          param_specs, prefill_logits)
from repro.parallel import sharding as shd                    # noqa: E402
from repro.roofline import analysis                           # noqa: E402
from repro.training import (OptConfig, TrainConfig,           # noqa: E402
                            make_baseline_step,
                            make_compressed_step,
                            init_compressed_opt_state)
from repro.training import optimizer as optm                  # noqa: E402


def cell_rules(cfg: ModelConfig, shape: ShapeConfig, mesh) -> shd.ShardingRules:
    """Per-cell sharding rules (DESIGN.md: rules, not model code, change
    with the layout)."""
    extra = {}
    fsdp = True
    if shape.kind == "decode" and cfg.serve_params_tp_only:
        fsdp = False
    if shape.kind == "decode":
        model_size = mesh.shape["model"]
        if cfg.num_kv_heads % model_size != 0:
            # GQA kv heads don't divide TP: shard the cache sequence dim
            # instead (flash-decode style partial attention + combine).
            extra["kv_seq"] = "model"
        if shape.global_batch == 1:
            # long-context: batch can't shard; spread cache over dp too.
            extra["kv_seq"] = ("data", "model")
            extra["batch"] = None
    return shd.make_rules(fsdp_params=fsdp, extra=extra)


def _param_sds(cfg: ModelConfig, mesh):
    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    specs = param_specs(cfg)
    rules = shd.get_rules()

    def mk(leaf, spec):
        ns = NamedSharding(mesh, rules.spec(spec, shape=leaf.shape,
                                            param=True))
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=ns)

    return _tree_mk(shapes, specs, mk)


def _tree_mk(shapes, specs, mk):
    flat_shapes, treedef = jax.tree.flatten(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=shd.is_spec_leaf)
    assert len(flat_shapes) == len(flat_specs), (
        len(flat_shapes), len(flat_specs))
    return jax.tree.unflatten(
        treedef, [mk(l, s) for l, s in zip(flat_shapes, flat_specs)])


def _batch_sds(cfg: ModelConfig, shape: ShapeConfig, mesh):
    structs = input_shape_structs(
        cfg.vocab_size, shape.seq_len, shape.global_batch,
        prefix_len=cfg.frontend_prefix_len, d_model=cfg.d_model,
        dtype=jnp.dtype(cfg.dtype))
    rules = shd.get_rules()

    def mk(leaf):
        spec = rules.spec(("batch",) + (None,) * (len(leaf.shape) - 1),
                          shape=leaf.shape)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return {k: mk(v) for k, v in structs.items()}


def _microbatches(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                      if a in mesh.axis_names]))
    local_b = max(1, shape.global_batch // dp)
    # target <= 2 sequences per microbatch per rank for the 4k trains
    n = max(1, min(local_b, local_b // 2))
    while local_b % n:
        n -= 1
    return n


def build_lowering(cfg: ModelConfig, shape: ShapeConfig, mesh,
                   comm: str = "baseline"):
    """Returns (jitted, example_args) ready to .lower()."""
    rules = cell_rules(cfg, shape, mesh)
    shd.set_rules(rules)

    if shape.kind == "train":
        opt_cfg = OptConfig(moment_dtype="bfloat16")
        train_cfg = TrainConfig(microbatches=_microbatches(cfg, shape, mesh))
        params_sds = _param_sds(cfg, mesh)
        batch_sds = _batch_sds(cfg, shape, mesh)
        if comm in ("qlc", "e4m3"):
            from repro.comm import CommConfig, plan_for_tables
            from repro.core import TABLE1, build_tables, distributions
            counts = distributions.grad_counts(1 << 20)
            tables = build_tables(counts, TABLE1)
            plan = plan_for_tables(tables, counts, chunk_symbols=1024)
            comm_cfg = CommConfig.from_plan(plan)
            if comm == "e4m3":
                comm_cfg = dataclasses.replace(comm_cfg, enabled=False)
            # compressed mode: params dp-replicated (TP only)
            shd.set_rules(shd.make_rules(fsdp_params=False))
            params_sds = _param_sds(cfg, mesh)
            step = make_compressed_step(cfg, opt_cfg, train_cfg, mesh,
                                        tables, comm_cfg)
            opt_shapes = jax.eval_shape(
                lambda: init_compressed_opt_state(
                    cfg, mesh, train_cfg, comm_cfg, opt_cfg))
            dp_axes = tuple(a for a in ("pod", "data")
                            if a in mesh.axis_names)
            opt_sds = {
                "m": jax.ShapeDtypeStruct(
                    opt_shapes["m"].shape, opt_shapes["m"].dtype,
                    sharding=NamedSharding(
                        mesh, P(*(dp_axes + ("model", None))))),
                "v": jax.ShapeDtypeStruct(
                    opt_shapes["v"].shape, opt_shapes["v"].dtype,
                    sharding=NamedSharding(
                        mesh, P(*(dp_axes + ("model", None))))),
                "step": jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, P())),
            }
        else:
            step = make_baseline_step(cfg, opt_cfg, train_cfg)
            opt_shapes = jax.eval_shape(
                lambda p: optm.init_state(p, opt_cfg), params_sds)
            specs = param_specs(cfg)
            rules_ = shd.get_rules()

            def mk_opt(leaf, spec):
                ns = NamedSharding(mesh, rules_.spec(
                    spec, shape=leaf.shape, param=True))
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=ns)

            opt_sds = {
                "m": _tree_mk(opt_shapes["m"], specs, mk_opt),
                "v": _tree_mk(opt_shapes["v"], specs, mk_opt),
                "step": jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, P())),
            }
        return jax.jit(step), (params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        params_sds = _param_sds(cfg, mesh)
        batch_sds = _batch_sds(cfg, shape, mesh)

        def prefill_step(params, batch):
            return prefill_logits(params, cfg, batch["tokens"],
                                  batch.get("prefix_emb"))

        return jax.jit(prefill_step), (params_sds, batch_sds)

    # decode: one new token against a seq_len-deep cache/state
    params_sds = _param_sds(cfg, mesh)
    weight_codec = None
    if comm in ("qlc", "e4m3"):
        # paper technique on serving: weight gathers move QLC/e4m3 wire
        from repro.comm import plan_for_tables
        from repro.comm.weights import wire_shape_structs
        from repro.core import TABLE1, build_tables, distributions
        counts = distributions.ffn1_counts(1 << 20)
        w_tables = build_tables(counts, TABLE1)
        w_plan = plan_for_tables(w_tables, counts, chunk_symbols=1024)
        wired, weight_codec = wire_shape_structs(
            jax.eval_shape(lambda k: init_params(cfg, k),
                           jax.random.PRNGKey(0))["groups"],
            w_tables, w_plan.capacity_words, mode=comm, mesh=mesh)
        params_sds = dict(params_sds)
        params_sds["groups"] = wired
    b = shape.global_batch
    states_shapes = jax.eval_shape(
        lambda: init_decode_states(cfg, b, shape.seq_len))
    kinds_specs = decode_states_specs(cfg)
    rules_ = shd.get_rules()

    def mk_state(leaf, spec):
        ns = NamedSharding(mesh, rules_.spec(spec, shape=leaf.shape))
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=ns)

    states_sds = _tree_mk(states_shapes, kinds_specs, mk_state)
    dp_spec = rules_.spec(("batch", None), shape=(b, 1))
    tok_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                   sharding=NamedSharding(mesh, dp_spec))
    pos_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                   sharding=NamedSharding(mesh, dp_spec))

    def serve_step(params, states, tokens, positions):
        return decode_step(params, cfg, tokens, states, positions,
                           weight_codec=weight_codec)

    return (jax.jit(serve_step, donate_argnums=(1,)),
            (params_sds, states_sds, tok_sds, pos_sds))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             comm: str = "baseline", overrides: dict | None = None,
             hlo_out: str | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        moe_ov = {k[4:]: v for k, v in overrides.items()
                  if k.startswith("moe.")}
        top = {k: v for k, v in overrides.items()
               if not k.startswith("moe.")}
        if moe_ov:
            top["moe"] = dataclasses.replace(cfg.moe, **moe_ov)
        cfg = dataclasses.replace(cfg, **top)
    shape = {s.name: s for s in shapes_for(cfg)}[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi_pod_2x16x16" if multi_pod else "single_pod_16x16"
    chips = int(np.prod(mesh.devices.shape))

    t0 = time.time()
    with shd.use_mesh(mesh):
        jitted, args = build_lowering(cfg, shape, mesh, comm)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        print("memory_analysis:", mem)              # proves it fits
        cost = compiled.cost_analysis()
        print("cost_analysis flops:", cost.get("flops"),
              "bytes:", cost.get("bytes accessed"))
        hlo = compiled.as_text()
        if hlo_out:
            with gzip.open(hlo_out, "wt") as f:
                f.write(hlo)

    mem_stats = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_stats[attr] = int(v)

    terms = analysis.from_compiled(arch, shape, mesh_name, chips, cost,
                                   hlo, cfg, mem_stats)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "comm": comm, "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_stats,
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "roofline": terms.to_dict(),
        "ok": True,
    }
    shd.set_rules(None)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--comm", default="baseline",
                    choices=["baseline", "qlc", "e4m3"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (python literal)")
    ap.add_argument("--sweep", action="store_true",
                    help="run every (arch x shape) cell in subprocesses")
    ap.add_argument("--out-dir", default="results/dryrun")
    args = ap.parse_args()

    if args.sweep:
        os.makedirs(args.out_dir, exist_ok=True)
        cells = []
        for arch in ASSIGNED:
            for s in shapes_for(get_config(arch)):
                cells.append((arch, s.name))
        for arch, shape in cells:
            tag = f"{arch}__{shape}__" + (
                "multi" if args.multi_pod else "single")
            if args.comm != "baseline":
                tag += f"__{args.comm}"
            out = os.path.join(args.out_dir, tag + ".json")
            if os.path.exists(out):
                print("skip", tag)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--comm", args.comm,
                   "--out", out]
            if args.multi_pod:
                cmd.append("--multi-pod")
            print(">>>", tag, flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ,
                                    "PYTHONPATH": "src"})
            if r.returncode != 0:
                err = {"arch": arch, "shape": shape, "ok": False,
                       "mesh": ("multi_pod_2x16x16" if args.multi_pod
                                else "single_pod_16x16"),
                       "comm": args.comm,
                       "error": r.stderr[-4000:]}
                with open(out, "w") as f:
                    json.dump(err, f, indent=1)
                print("FAIL", tag)
                print(r.stderr[-2000:])
            else:
                print(r.stdout[-400:])
        return

    import ast
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v
    hlo_out = args.out.replace(".json", ".hlo.gz") if args.out else None
    result = run_cell(args.arch, args.shape, args.multi_pod, args.comm,
                      overrides, hlo_out=hlo_out)
    result["overrides"] = overrides
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("memory",)}, indent=1, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, default=str)


if __name__ == "__main__":
    main()
