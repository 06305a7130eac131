"""Continuous-batching serving example over the request-based Engine.

Requests are submitted to ``repro.serving.Engine`` and join/leave the
padded decode batch mid-flight — the request-based API that replaced
the legacy ``generate`` batch calls in PR 6. The driver below staggers
``--concurrent`` submissions across engine steps (two tenants, a
fairness cap) and asserts each request's tokens are IDENTICAL to
running it alone in a fresh single-slot engine: continuous batching is
a pure scheduling change.

With ``--wire qlc`` the weights are served from QLC wire: a codec
registry calibrates per-parameter codecs, the wire codec binds a
Channel (kernel toggle + placement made once), the serving manifest
round-trips the recipe through JSON, and the wire is opened through
the channel before serving.

With ``--kv-cache qlc`` every resident sequence block-pages its decode
states through ONE shared compressed :class:`~repro.serving.BlockPool`
(capacity measured in compressed bytes): per-layer codecs calibrate
lazily from the first prefill, identical prompt prefixes dedup pooled
blocks by container digest, and the per-request identity assert above
doubles as the lossless contract. (``--kv-cache e4m3`` additionally
quantizes blocks on eviction: smaller, but lossy like any fp8 cache.)

``--kv-paging async`` (with ``--kv-cache qlc``) moves paging off the
host: evicted blocks live in a device-resident arena, block decodes
are DMA-prefetched one admission window ahead, and the decode loop
keeps each window's greedy feedback on device (two host-to-device
transfers and one device-to-host per window, regardless of window
length). Tokens stay identical to sync paging; the prefetch
hit/stall counters print at the end.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch xlstm-125m
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import init_params
from repro.serving import (BlockPool, Engine, GenerationRequest,
                           KVCacheSpec)


def run_requests(params, cfg, prompts, budgets, tenants, *, max_seq_len,
                 max_batch, kv_spec=None, registry=None, pool=None,
                 stagger=2, fairness_cap=0.5, kv_paging="sync"):
    """Drive one engine over staggered submissions; returns the tokens
    per request plus the engine (for stats)."""
    eng = Engine(params, cfg, max_seq_len=max_seq_len,
                 max_batch=max_batch, kv_spec=kv_spec, registry=registry,
                 pool=pool, fairness_cap=fairness_cap, kv_paging=kv_paging)
    handles = []
    pending = list(zip(prompts, budgets, tenants))
    while pending or (handles and any(
            eng.poll(h).state in ("waiting", "running") for h in handles)):
        for prompt, budget, tenant in pending[:stagger]:
            handles.append(eng.submit(GenerationRequest(
                prompt=prompt, max_new_tokens=budget, tenant=tenant)))
        pending = pending[stagger:]
        eng.step()
    return [eng.poll(h).tokens for h in handles], eng


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-coder-33b",
                    help="any assigned arch; a reduced config is served")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (max concurrent sequences)")
    ap.add_argument("--concurrent", type=int, default=None,
                    help="requests to submit (default: batch + 2, so "
                         "requests queue and join mid-flight)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--wire", default="none", choices=["none", "qlc"],
                    help="'qlc' serves from compressed weights opened "
                         "through a channel-bound wire codec")
    ap.add_argument("--kv-cache", default="none",
                    choices=["none", "qlc", "e4m3"],
                    help="'qlc' pages decode states through a shared "
                         "compressed block pool (token-identical); "
                         "'e4m3' also quantizes blocks (lossy)")
    ap.add_argument("--kv-block", type=int, default=4,
                    help="tokens per paged-cache block")
    ap.add_argument("--kv-paging", default="sync",
                    choices=["sync", "async"],
                    help="'async' pages blocks through the device-"
                         "resident arena: windowed device decode + DMA-"
                         "prefetched block decodes (requires "
                         "--kv-cache qlc)")
    args = ap.parse_args()
    if args.kv_paging == "async" and args.kv_cache != "qlc":
        ap.error("--kv-paging async requires --kv-cache qlc")
    n_req = args.concurrent or args.batch + 2

    cfg = reduced(get_config(args.arch), frontend_prefix_len=0,
                  frontend=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_seq_len = args.prompt_len + args.new_tokens + 8

    reg = None
    if args.wire == "qlc":
        from repro.comm.calibrate import histogram_of_tree
        from repro.core import CodecRegistry
        from repro.serving import (codec_from_manifest,
                                   compress_params_for_serving,
                                   open_params, serving_manifest)
        reg = CodecRegistry()
        reg.register("default", histogram_of_tree(params))
        wired, wc = compress_params_for_serving(params, reg)
        # manifest round trip — what a serving host reloads (registry,
        # per-leaf scheme-ids, AND the channel placement)
        wc2 = codec_from_manifest(serving_manifest(wc))
        ch = wc2.channel()
        print(f"serving {len(wc2.meta)} QLC-wired leaves via {ch}")
        params = jax.jit(lambda w: open_params(w, wc2, channel=ch))(wired)

    # staggered multi-tenant request mix: half the prompts share a
    # prefix (the prefix-sharing dedup case), budgets vary
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, args.prompt_len)
    prompts, budgets, tenants = [], [], []
    for i in range(n_req):
        if i % 2 == 0:
            p = shared.copy()
        else:
            p = np.concatenate([shared[:args.prompt_len // 2],
                                rng.integers(0, cfg.vocab_size,
                                             args.prompt_len -
                                             args.prompt_len // 2)])
        prompts.append(p.astype(np.int32))
        budgets.append(args.new_tokens - (i % 3))
        tenants.append("alice" if i % 2 == 0 else "bob")

    kv_spec = None
    pool = None
    kv_reg = None
    if args.kv_cache != "none":
        from repro.core import CodecRegistry
        # async paging needs the fixed-geometry wire (compile-time
        # container offsets), so it forces exact_capacity=False
        kv_spec = KVCacheSpec(block_tokens=args.kv_block,
                              mode=args.kv_cache,
                              exact_capacity=args.kv_paging != "async")
        pool = BlockPool(1 << 30)
        kv_reg = reg if reg is not None else CodecRegistry()

    outs, eng = run_requests(
        params, cfg, prompts, budgets, tenants, max_seq_len=max_seq_len,
        max_batch=args.batch, kv_spec=kv_spec, registry=kv_reg, pool=pool,
        kv_paging=args.kv_paging)
    st = eng.stats()
    print(f"arch={cfg.name} slots={args.batch} requests={n_req} "
          f"prompt={args.prompt_len}")
    print(f"engine: {st['steps']} steps, "
          f"{st['ms_per_token_prefill']:.1f} ms/tok prefill, "
          f"{st['ms_per_token_decode']:.1f} ms/tok decode "
          f"(batched, CPU)")
    assert st["requests"]["finished"] == n_req, st["requests"]

    # the serving contract: each request's tokens are identical to
    # running it ALONE (single-slot dense engine) — continuous batching
    # and, for --kv-cache qlc, pooled compressed paging change nothing
    check = args.kv_cache != "e4m3"   # e4m3 paging is deliberately lossy
    if check:
        for prompt, budget, got in zip(prompts, budgets, outs):
            solo, _ = run_requests(params, cfg, [prompt], [budget],
                                   ["solo"], max_seq_len=max_seq_len,
                                   max_batch=1)
            assert np.array_equal(got, solo[0]), \
                "engine output diverged from isolated run"
        print(f"{n_req} requests token-identical to isolated runs OK")

    if pool is not None:
        ps = st["pool"]
        dense = st["peak_dense_logical_bytes"]
        print(f"kv-cache={args.kv_cache} block={args.kv_block}: "
              f"peak {ps['peak_referenced_bytes']} compressed B pinned "
              f"vs {dense} dense B "
              f"({ps['dedup_hits']} prefix dedup hits, "
              f"{ps['unique_blocks']} unique blocks, "
              f"{st['kv']['raw_sections']} raw sections)")
        if ps["peak_referenced_bytes"]:
            print(f"concurrent-capacity ratio "
                  f"{dense / ps['peak_referenced_bytes']:.2f}x")
        if args.kv_paging == "async":
            pf = st["prefetch"]
            print(f"async paging: {st['async']['windows']} decode "
                  f"windows ({st['async']['d2h_per_window']:.0f} d2h "
                  f"per window), prefetch {pf['hits']}/{pf['scheduled']} "
                  f"hits ({pf['stalled']} stalled, "
                  f"{pf['bytes_prefetched']} B prefetched, "
                  f"overlap {pf['overlap_fraction']:.3f})")
    print("sample:", np.asarray(outs[0])[:12], "...")
    print("OK")


if __name__ == "__main__":
    main()
