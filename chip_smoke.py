#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, in one process.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --four-chips        # a 2x2 v5e host

One chip, in order:

  kernels  random values from ``--seed`` the size of one phi3-mini-3.8b
           FFN weight (3072 x 8192) through the compiled kernels:
           fused quantize→encode, fused decode→dequantize (+ accumulate),
           plain encode/decode at K=1024, and plain and DMA-prefetch
           decode at the KV cache's K=256. Every output must be
           bit-identical to ``repro.core.codec`` / ``repro.quant.e4m3``
           run on the same chip.
  serving  phi3-mini-3.8b at its published widths, all 32 layers, bf16
           weights from the seed, through the serving launcher's own
           functions: 6 requests of 512 prompt tokens on 4 slots, 32 new
           tokens each, with the dense KV cache, the QLC-paged cache
           (sync) and the QLC-paged cache with async device paging. The
           tokens of the three must be identical.

``--four-chips`` runs only data-parallel training on a data=4 mesh: the
compressed step (QLC gradient reduce-scatter and parameter all-gather,
transport ``auto``) beside the baseline step, phi3-mini-3.8b widths
with depth cut to ``FOUR_CHIP_LAYERS``. Its codecs are calibrated on
one chip first.

Each phase prints what it checked. The last line of stdout is one JSON
object naming the device; it is printed only when every phase passed.
The script exits non-zero without it when no TPU is found, when the
repository's ``src/`` is not beside it, when any phase fails, or when
a phase outlasts its wall-time bound (``*_S`` below).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

FFN_SHAPE = (3072, 8192)          # phi3-mini-3.8b d_model x d_ff
KV_CHUNKS = 4096                  # K=256 chunks in the KV decode check
SERVE_ARCH = "phi3-mini-3.8b"
SERVE_SLOTS, SERVE_REQUESTS = 4, 6
PROMPT_LEN, NEW_TOKENS, KV_BLOCK = 512, 32, 128
FOUR_CHIP_LAYERS = 4              # of 32: f32 params + grads on each chip
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 512, 8, 4
# Wall-time bounds (s). Past one, every thread's stack is dumped to
# stderr and the process exits non-zero, so a device call that never
# returns fails the run instead of holding the chip.
KERNELS_S, SERVING_S = 300, 700
# --four-chips: calibration, then each step kind (compile included).
CALIBRATE_S, STEPS_S = 90, {"baseline": 90, "compressed": 270}


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _same(a, b) -> bool:
    import jax.numpy as jnp
    return a.shape == b.shape and a.dtype == b.dtype and bool(
        jnp.array_equal(a, b))


@contextlib.contextmanager
def deadline(seconds: float):
    """Exit the process (non-zero, stacks on stderr) if the block runs
    longer than ``seconds``."""
    faulthandler.dump_traceback_later(seconds, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  ok: {what}")


# --------------------------------------------------------------------------
# Kernels
# --------------------------------------------------------------------------

def kernels_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CodecRegistry, codec
    from repro.kernels import ops
    from repro.quant import e4m3

    kx, ka = jax.random.split(jax.random.PRNGKey(seed))
    k = 1024
    n = FFN_SHAPE[0] * FFN_SHAPE[1] // k
    x = jax.random.normal(kx, (n, k), jnp.float32) * 0.02
    codes, scales = jax.jit(e4m3.quantize_block32)(x)
    counts = np.bincount(np.asarray(codes).reshape(-1), minlength=256)
    tables = CodecRegistry().register("smoke", counts).tables
    cw = codec.worst_case_words(k, tables.max_code_length)
    log(f"kernels: {n} chunks x K={k} ({n * k} symbols), CW={cw}")

    # Oracles: the pure-JAX codec, compiled for the same chip.
    ref_enc = jax.jit(lambda c: codec.encode_chunks(c, tables, cw))
    ref_dec = jax.jit(lambda w: codec.decode_chunks(w, tables, k))
    ref_deq = jax.jit(e4m3.dequantize_block32)
    words_ref, nbits_ref = ref_enc(codes)
    sym_ref = ref_dec(words_ref)
    check(_same(sym_ref, codes), "core.codec round trip is lossless")
    vals_ref = ref_deq(sym_ref, scales)

    words, nbits, sc, cd = jax.jit(lambda v: ops.quantize_encode(
        v, tables, cw, emit_codes=True))(x)
    check(_same(words, words_ref) and _same(nbits, nbits_ref)
          and _same(sc, scales) and _same(cd, codes),
          "quantize_encode: words, nbits, scales, symbols == "
          "e4m3.quantize_block32 + codec.encode_chunks")
    log(f"  coding rate {float(jnp.sum(nbits, dtype=jnp.float32)) / (n * k):.4f}"
        " bits/symbol")

    vals = jax.jit(lambda w, s: ops.decode_dequantize(w, s, tables, k))(
        words, scales)
    check(_same(vals, vals_ref),
          "decode_dequantize == codec.decode_chunks + dequantize_block32")
    acc = jax.random.normal(ka, (n, k), jnp.float32)
    summed = jax.jit(lambda a, w, s: ops.decode_dequantize_accumulate(
        a, w, s, tables, k))(acc, words, scales)
    check(_same(summed, jax.jit(jnp.add)(acc, vals_ref)),
          "decode_dequantize_accumulate == acc + reference values")
    check(_same(jax.jit(lambda c: ops.encode(c, tables, cw))(codes)[0],
                words_ref), "encode == codec.encode_chunks")
    check(_same(jax.jit(lambda w: ops.decode(w, tables, k))(words), codes),
          "decode == codec.decode_chunks")

    kv = 256
    kv_syms = codes.reshape(-1, kv)[:KV_CHUNKS]
    kv_cw = codec.worst_case_words(kv, tables.max_code_length)
    kv_words, _ = jax.jit(lambda c: codec.encode_chunks(c, tables, kv_cw))(
        kv_syms)
    check(_same(jax.jit(lambda c: ops.encode(c, tables, kv_cw))(kv_syms)[0],
                kv_words), f"encode K={kv} == codec.encode_chunks")
    check(_same(jax.jit(lambda w: ops.decode(w, tables, kv))(kv_words),
                kv_syms), f"decode K={kv} == symbols")
    check(_same(jax.jit(lambda w: ops.decode_block_async(w, tables, kv))(
        kv_words), kv_syms), f"decode_block_async K={kv} == symbols")


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------

def serving_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.launch.mesh import make_device_mesh
    from repro.parallel import sharding as shd

    cfg = serve.serving_config(SERVE_ARCH)
    mesh = make_device_mesh()
    with shd.use_mesh(mesh):
        params = serve.init_serving_params(cfg, seed)
        leaves = jax.tree.leaves(params)
        n_params = sum(x.size for x in leaves)
        check({str(x.dtype) for x in leaves} == {"bfloat16"},
              f"{SERVE_ARCH}: {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
              f"{n_params} params, all bf16")
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed + 1), (SERVE_REQUESTS, PROMPT_LEN), 0,
            cfg.vocab_size))
        tokens = {}
        for name, mode, paging in (("dense", "none", "sync"),
                                   ("qlc-sync", "qlc", "sync"),
                                   ("qlc-async", "qlc", "async")):
            t0 = time.time()
            outs, stats, _ = serve.serve_requests(
                params, cfg, prompts, batch=SERVE_SLOTS,
                new_tokens=NEW_TOKENS,
                kv_spec=serve.kv_cache_spec(mode, KV_BLOCK, paging),
                kv_paging=paging, mesh=mesh)
            tokens[name] = np.stack([np.asarray(o.tokens) for o in outs])
            extra = ""
            if mode == "qlc":
                extra = (f", pool peak "
                         f"{stats['pool']['peak_referenced_bytes']} B vs "
                         f"{stats['peak_dense_logical_bytes']} dense B")
            if paging == "async":
                pf = stats["prefetch"]
                extra += (f", prefetch {pf['hits']}/{pf['scheduled']} hits"
                          f", {stats['async']['windows']} windows")
            log(f"  {name}: {tokens[name].shape[0]} requests x "
                f"{tokens[name].shape[1]} tokens in "
                f"{time.time() - t0:.1f} s wall incl. compile{extra}")
        check(all(np.array_equal(tokens["dense"], t)
                  for t in tokens.values()),
              "dense, qlc-sync and qlc-async tokens identical")


# --------------------------------------------------------------------------
# Four chips: data-parallel training, compressed vs baseline
# --------------------------------------------------------------------------

def four_chip_setup(devices, layers: int = FOUR_CHIP_LAYERS):
    """(cfg, mesh, opt_cfg, train_cfg, data): the cut phi3-mini config on
    a data-parallel mesh over ``devices`` (real or described)."""
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticDataset
    from repro.launch.mesh import make_device_mesh
    from repro.training import OptConfig, TrainConfig

    cfg = dataclasses.replace(get_config(SERVE_ARCH), num_layers=layers)
    mesh = make_device_mesh(devices=devices, model=1)
    opt_cfg = OptConfig(lr=1e-4, warmup_steps=1, total_steps=TRAIN_STEPS)
    train_cfg = TrainConfig(batch_axes=("data",))
    data = SyntheticDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
        global_batch=TRAIN_BATCH))
    return cfg, mesh, opt_cfg, train_cfg, data


def train_steps(cfg, mesh, opt_cfg, train_cfg, registry):
    """The jitted baseline and compressed steps, each updating its
    params and optimizer state in place."""
    import jax

    from repro.training import make_baseline_step, make_compressed_step

    # The compiled kernels encode and decode the wire on the chip.
    wire = dataclasses.replace(registry["grads"].config(), use_kernels=True)
    return (jax.jit(make_baseline_step(cfg, opt_cfg, train_cfg),
                    donate_argnums=(0, 1)),
            jax.jit(make_compressed_step(cfg, opt_cfg, train_cfg, mesh,
                                         registry, wire, transport="auto"),
                    donate_argnums=(0, 1)))


def four_chip_phase(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.comm import calibrate_for_gradients
    from repro.comm.calibrate import histogram_of_tree
    from repro.core import CodecRegistry
    from repro.models import init_params
    from repro.parallel import sharding as shd
    from repro.training import init_compressed_opt_state
    from repro.training import optimizer as optm

    devices = jax.devices()
    cfg, mesh, opt_cfg, train_cfg, data = four_chip_setup(devices)
    log(f"train: {SERVE_ARCH} widths, {cfg.num_layers} of 32 layers, "
        f"mesh {dict(mesh.shape)}, seq {TRAIN_SEQ}, global batch "
        f"{TRAIN_BATCH}")

    # The codecs are calibrated on one chip, off the mesh: the gradient
    # of the seeded weights on the first data shard's rows, and the
    # weights themselves.
    t0 = time.time()
    with deadline(CALIBRATE_S):
        one = jax.jit(init_params, static_argnums=0,
                      out_shardings=SingleDeviceSharding(devices[0]))
        params = one(cfg, jax.random.PRNGKey(seed))
        rows = TRAIN_BATCH // len(devices)
        b0 = {k: jax.device_put(v[:rows], devices[0])
              for k, v in data.batch_at(0).items()}
        tables, plan = calibrate_for_gradients(cfg, params, b0)
        registry = CodecRegistry()
        registry.register_tables("grads", tables, plan)
        registry.register("params", histogram_of_tree(params),
                          chunk_symbols=plan.chunk_symbols)
        del params, b0
    log(f"  grads codec: {plan.expected_bits_per_symbol:.4f} planned "
        f"bits/symbol, K={plan.chunk_symbols}, calibrated on one chip in "
        f"{time.time() - t0:.1f} s")

    init = jax.jit(init_params, static_argnums=0,
                   out_shardings=NamedSharding(mesh, P()))
    with shd.use_mesh(mesh):
        base, comp = train_steps(cfg, mesh, opt_cfg, train_cfg, registry)
        losses = {}
        by_data = NamedSharding(mesh, P("data"))
        for name, step in (("baseline", base), ("compressed", comp)):
            t0 = time.time()
            losses[name] = []
            with deadline(STEPS_S[name]):
                # Both start from the same seeded weights.
                p = init(cfg, jax.random.PRNGKey(seed))
                o = (jax.jit(lambda q: optm.init_state(q, opt_cfg))(p)
                     if name == "baseline" else init_compressed_opt_state(
                         cfg, mesh, train_cfg, registry, opt_cfg))
                for i in range(TRAIN_STEPS):
                    batch = {k: jax.device_put(v, by_data)
                             for k, v in data.batch_at(i).items()}
                    p, o, m = step(p, o, batch)
                    losses[name].append(float(np.asarray(m["loss"])))
                    if "ok" in m:
                        check(bool(np.asarray(m["ok"])),
                              f"{name} step {i}: escape pools held")
            used = set().union(*(x.sharding.device_set
                                 for x in jax.tree.leaves(p)))
            check(used == set(devices),
                  f"{name}: params live on all {len(devices)} devices "
                  f"after {TRAIN_STEPS} steps "
                  f"({time.time() - t0:.1f} s wall incl. compile)")
            log(f"  {name} losses: {losses[name]}")
            del p, o
    log(f"  peak_bytes_in_use per device: "
        f"{[peak_bytes(d) for d in devices]}")
    lb, lc = losses["baseline"], losses["compressed"]
    check(all(np.isfinite(lb + lc)), "all losses finite")
    check(abs(lc[0] - lb[0]) <= 1e-3 * abs(lb[0]),
          "first losses agree (same params, same batch)")
    check(all(abs(c - b) <= 0.05 * abs(b) for b, c in zip(lb, lc)),
          "compressed losses track baseline within 5%")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=4 training phase")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC} holds no repro package", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.runtime import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x {len(devices)}, compile cache "
        f"{enable_compile_cache()}")

    t0 = time.time()
    phases = ([(four_chip_phase, None)] if args.four_chips
              else [(kernels_phase, KERNELS_S), (serving_phase, SERVING_S)])
    for phase, limit in phases:
        t = time.time()
        with (deadline(limit) if limit else contextlib.nullcontext()):
            phase(args.seed)
        log(f"{phase.__name__}: passed in {time.time() - t:.1f} s; "
            f"peak_bytes_in_use {peak_bytes(dev)}")
    log(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
