"""Serving launcher: continuous-batching request engine.

Runs on the devices present (``launch.mesh.make_device_mesh``): one
v5e chip serves phi3-mini-3.8b at its published widths, all 32 layers,
with bf16 weights created directly in bf16 (≈7.6 GB of the chip's
16 GB), the rest of HBM left to the KV cache.

Requests go through ``repro.serving.Engine`` (PR 6): submit
``GenerationRequest``s, drive ``step()``, ``poll()`` the tokens. The
engine owns one padded decode batch that requests join and leave
mid-flight — the legacy one-``generate``-call-per-batch path is gone
from the launcher (the deprecated wrappers remain in ``repro.serving``
for callers mid-migration).

``--wire qlc`` serves from QLC-compressed weights: the parameter stack
is stored as block-32 e4m3 + QLC words and opened through a
channel-bound fused decode (``repro.comm.channel`` + the serving wire
codec) before the engine starts — the production path where weight
bytes move compressed.

``--kv-cache qlc`` block-pages every resident sequence's decode states
through ONE shared compressed block pool
(``repro.serving.BlockPool``): per-layer codecs calibrated lazily from
the first prefill, blocks encoded to QLC containers on eviction,
decoded from the (prefix-deduped) pooled bytes on access — losslessly,
so tokens match the dense run. ``--kv-block`` sets the block size.

Examples:
  python -m repro.launch.serve --arch phi3-mini-3.8b
  python -m repro.launch.serve --arch musicgen-medium --reduced \\
      --batch 8 --new-tokens 32 --wire qlc --kv-cache qlc
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced as make_reduced
from repro.launch.mesh import make_device_mesh
from repro.models import init_params
from repro.parallel import sharding as shd
from repro.runtime import enable_compile_cache
from repro.serving import BlockPool, Engine, GenerationRequest, KVCacheSpec


def serving_config(arch: str, *, reduced: bool = False):
    """``arch``'s config as served: its own widths and depth (or the
    reduced smoke preset), weights in the model's published dtype."""
    cfg = get_config(arch)
    if reduced:
        cfg = dataclasses.replace(make_reduced(cfg), frontend=None,
                                  frontend_prefix_len=0)
    return dataclasses.replace(cfg, param_dtype=cfg.dtype)


def init_serving_params(cfg, seed: int = 0):
    """Random weights made on the device in ``cfg.param_dtype`` by one
    jitted init, so no wider copy of them is ever resident."""
    return jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))


def kv_cache_spec(mode: str, block_tokens: int, paging: str):
    """The paged-cache spec the launcher serves ``--kv-cache mode`` with
    (None for the dense cache)."""
    if mode == "none":
        return None
    # async needs compile-time container offsets
    return KVCacheSpec(block_tokens=block_tokens, mode=mode,
                       exact_capacity=paging != "async")


def serve_requests(params, cfg, prompts, *, batch: int, new_tokens: int,
                   kv_spec=None, kv_paging: str = "sync", mesh=None):
    """Submit one request per prompt row, run the engine to completion.

    Returns ``(outs, stats, seconds)``; ``outs[i].tokens`` are prompt
    ``i``'s generated tokens.
    """
    eng = Engine(params, cfg, max_seq_len=prompts.shape[1] + new_tokens + 8,
                 max_batch=batch, kv_spec=kv_spec,
                 pool=BlockPool(1 << 30) if kv_spec is not None else None,
                 kv_paging=kv_paging, mesh=mesh)
    t0 = time.time()
    handles = [eng.submit(GenerationRequest(
        prompt=p, max_new_tokens=new_tokens)) for p in prompts]
    eng.run()
    dt = time.time() - t0
    outs = [eng.poll(h) for h in handles]
    assert all(s.state == "finished" for s in outs), \
        [(s.request_id, s.state, s.error) for s in outs]
    return outs, eng.stats(), dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (max concurrent sequences)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to submit (default: batch + 2)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--wire", default="none", choices=["none", "qlc"],
                    help="'qlc' stores weights as QLC wire and decodes "
                         "them through a bound channel")
    ap.add_argument("--kv-cache", default="none",
                    choices=["none", "qlc", "e4m3"],
                    help="page decode states through a shared compressed "
                         "block pool ('qlc' lossless, 'e4m3' quantized)")
    ap.add_argument("--kv-block", type=int, default=128,
                    help="tokens per paged-cache block")
    ap.add_argument("--kv-paging", default="sync",
                    choices=["sync", "async"],
                    help="'async' keeps evicted blocks in a device-"
                         "resident arena and decodes them via DMA "
                         "prefetch behind an on-device decode window "
                         "(requires --kv-cache qlc)")
    args = ap.parse_args()
    if args.kv_paging == "async" and args.kv_cache != "qlc":
        ap.error("--kv-paging async requires --kv-cache qlc")
    n_req = args.requests or args.batch + 2

    enable_compile_cache()
    cfg = serving_config(args.arch, reduced=args.reduced)
    mesh = make_device_mesh()
    with shd.use_mesh(mesh):
        params = init_serving_params(cfg)
        if args.wire == "qlc":
            from repro.comm.calibrate import histogram_of_tree
            from repro.core import CodecRegistry
            from repro.serving import (compress_params_for_serving,
                                       open_params)
            reg = CodecRegistry()
            reg.register("default", histogram_of_tree(params))
            wired, wc = compress_params_for_serving(params, reg)
            ch = wc.channel()          # local open, fused kernel decode
            print(f"weight wire: {len(wc.meta)} compressed leaves, "
                  f"channel {ch}")
            params = jax.jit(
                lambda w: open_params(w, wc, channel=ch))(wired)

        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (n_req, args.prompt_len), 0,
            cfg.vocab_size))
        outs, st, dt = serve_requests(
            params, cfg, prompts, batch=args.batch,
            new_tokens=args.new_tokens,
            kv_spec=kv_cache_spec(args.kv_cache, args.kv_block,
                                  args.kv_paging),
            kv_paging=args.kv_paging,
            mesh=mesh if not args.reduced else None)

        if args.kv_cache == "qlc":
            # the lossless contract: pooled compressed paging is
            # token-identical to a dense single-request run
            solo, _, _ = serve_requests(params, cfg, prompts[:1], batch=1,
                                        new_tokens=args.new_tokens)
            assert np.array_equal(outs[0].tokens, solo[0].tokens), \
                "qlc KV cache must be token-identical"
            ps = st["pool"]
            print(f"kv-cache=qlc: peak {ps['peak_referenced_bytes']} "
                  f"compressed B pinned vs "
                  f"{st['peak_dense_logical_bytes']} dense B, "
                  f"{ps['dedup_hits']} dedup hits")
            if args.kv_paging == "async":
                pf = st["prefetch"]
                print(f"async paging: {st['async']['windows']} windows, "
                      f"prefetch {pf['hits']}/{pf['scheduled']} hits, "
                      f"{pf['stalled']} stalled, "
                      f"overlap {pf['overlap_fraction']:.3f}")

    toks = sum(len(s.tokens) for s in outs)
    print(f"{n_req} requests / {toks} tokens in {dt*1e3:.0f}ms "
          f"({st['ms_per_token_prefill']:.1f} ms/tok prefill, "
          f"{st['ms_per_token_decode']:.1f} ms/tok decode)")
    print("first sequence:", np.asarray(outs[0].tokens)[:16])


if __name__ == "__main__":
    main()
