"""Benchmark: compressed KV-cache paging (serving decode states).

Builds REAL decode states (a reduced attention arch, bf16 cache — the
production dtype — prefilled from its own prompt), calibrates the
per-layer ``kv/layer{i}`` codecs, and pushes one full block per layer
through the paged cache's encode → container → decode round trip.

Rows:

* ``kv_cache_wire`` — compressed vs dense cold-cache bytes/token
  through the real container wire. The gated metric
  (``kv_compressed_vs_dense_ratio``) is the lossless byte-plane mode:
  it must beat the dense cache or the subsystem has no reason to
  exist. The e4m3 mode's ratio (quantized cache, the paper's native
  symbols) rides along as ``e4m3_vs_dense_ratio``.
* ``kv_block_decode`` — block decode-on-access latency (container →
  dense arrays), the per-token hot-path cost of a cache miss, split
  into ``host_frame_ms`` (header parse + section slicing) and
  ``device_decode_ms`` (the decode dispatch itself).
* ``kv_prefetch_overlap`` — sync vs async (device-resident arena +
  DMA-prefetched block decode) serving over the same request mix:
  per-token decode time ratio and the trace-derived fraction of block
  decode time hidden behind model compute. Both are gated
  (``check_regression.METRIC_GATES``).
* ``kv_concurrent_capacity`` — the serving engine's capacity win: N
  requests (with shared prompts, the realistic serving mix) run
  through ``repro.serving.Engine`` over ONE shared compressed
  :class:`~repro.serving.BlockPool`; the gated metric
  (``concurrent_capacity_ratio``) is peak DENSE bytes a per-sequence
  dense cache would pin divided by peak compressed bytes the pool
  actually pins (codec ratio × prefix-sharing dedup) — i.e. how many
  more concurrent sequences fit per device at fixed HBM. Engine
  ms/token prefill + decode ride along.
"""
from __future__ import annotations

import time

import numpy as np


def _states(cfg, batch, prompt_len, max_len):
    import jax
    from repro.models import init_decode_states, init_params
    from repro.serving import prefill
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size)
    states = init_decode_states(cfg, batch, max_len)
    _, states = prefill(params, cfg, prompts, states)
    return jax.block_until_ready(states)


def _blocks(cache, cfg, states, block_tokens):
    from repro.serving.kv_cache import calibration_arrays
    arrays = calibration_arrays(cfg, states, block_tokens)
    out = []
    for i in range(len(cfg.layer_kinds())):
        key = f"l{i}"
        out.append(cache.encode_block_arrays(
            cache.spec.layer_codec(i), key, arrays[key],
            start=0, tokens=block_tokens))
    return out, arrays


def run(n: int = 1 << 19):
    from repro.configs import get_config, reduced
    from repro.core.registry import CodecRegistry
    from repro.serving import KVCacheSpec, PagedKVCache, calibrate_cache

    cfg = reduced(get_config("phi3-mini-3.8b"), frontend=None,
                  frontend_prefix_len=0, dtype="bfloat16")
    block_tokens = max(16, min(256, int(n) // 512))
    prompt_len = block_tokens + 16
    states = _states(cfg, 2, prompt_len, prompt_len + 8)

    rows = []
    caches = {}
    for mode in ("qlc", "e4m3"):
        reg = CodecRegistry()
        spec = KVCacheSpec(block_tokens=block_tokens, mode=mode)
        calibrate_cache(reg, cfg, states, prompt_len, spec)
        caches[mode] = PagedKVCache(spec, cfg, reg)

    # ---- wire accounting (+ lossless round-trip check) -------------------
    t0 = time.perf_counter()
    blocks, arrays = _blocks(caches["qlc"], cfg, states, block_tokens)
    for b in blocks:
        decoded = caches["qlc"].decode_block_arrays(b)
        for orig, got in zip(arrays[b.layer], decoded):
            np.testing.assert_array_equal(
                np.asarray(orig).view(np.uint8),
                np.asarray(got).view(np.uint8))
    roundtrip_us = (time.perf_counter() - t0) * 1e6

    wire = sum(b.wire_bytes for b in blocks)
    dense = sum(b.dense_bytes for b in blocks)
    blocks_q, _ = _blocks(caches["e4m3"], cfg, states, block_tokens)
    wire_q = sum(b.wire_bytes for b in blocks_q)

    rows.append({
        "name": "kv_cache_wire",
        "us_per_call": roundtrip_us,
        "tokens_per_block": block_tokens,
        "compressed_bytes_per_token": round(wire / block_tokens, 1),
        "dense_bytes_per_token": round(dense / block_tokens, 1),
        "kv_compressed_vs_dense_ratio": round(wire / dense, 4),
        "e4m3_vs_dense_ratio": round(wire_q / dense, 4),
        "layers": len(blocks),
        "raw_sections": caches["qlc"].raw_sections,
    })

    # ---- decode-on-access latency ----------------------------------------
    # Split into its two halves (they regress independently): the HOST
    # framing walk (header parse + section slicing, pure numpy) and the
    # device decode dispatch (total minus framing). The old single
    # number hid host-side framing regressions behind decode noise.
    from repro.comm import container as qc

    def _host_frame_walk(b):
        buf = np.asarray(b.container)
        offset = 0
        while offset < buf.size:
            _, _, _, offset = qc.unpack_payload(buf, offset)

    cache = caches["qlc"]
    for b in blocks:                                   # warm
        cache.decode_block_arrays(b)
    reps = 3
    best = float("inf")
    best_frame = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for b in blocks:
            cache.decode_block_arrays(b)
        best = min(best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for b in blocks:
            _host_frame_walk(b)
        best_frame = min(best_frame, time.perf_counter() - t0)
    n_blocks = max(1, len(blocks))
    rows.append({
        "name": "kv_block_decode",
        "us_per_call": best * 1e6 / n_blocks,
        "host_frame_ms": round(best_frame * 1e3 / n_blocks, 4),
        "device_decode_ms": round((best - best_frame) * 1e3 / n_blocks,
                                  4),
        "blocks": len(blocks),
        "mb_per_s": round(dense / best / 1e6, 1),
    })

    # ---- concurrent capacity through the serving engine ------------------
    # A realistic serving mix (most requests share a prompt or a prompt
    # prefix) through one Engine over ONE shared pool. The capacity
    # ratio divides the dense bytes a per-sequence cache would pin at
    # peak by the compressed bytes the pool actually pins — the factor
    # by which concurrent residency grows at fixed HBM.
    import jax
    from repro.models import init_params
    from repro.serving import BlockPool, Engine, GenerationRequest

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt_len, max_new, max_batch = 12, 6, 4
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, prompt_len)
    prompts = [shared.copy() for _ in range(3)]
    prompts.append(np.concatenate([          # shared prefix, new tail
        shared[:prompt_len - 4],
        rng.integers(0, cfg.vocab_size, 4)]))
    prompts = [p.astype(np.int32) for p in prompts]

    pool = BlockPool(1 << 30)
    eng = Engine(params, cfg, max_seq_len=prompt_len + max_new + 4,
                 max_batch=max_batch,
                 kv_spec=KVCacheSpec(block_tokens=4, mode="qlc",
                                     hot_blocks=1),
                 registry=CodecRegistry(), pool=pool)
    t0 = time.perf_counter()
    handles = [eng.submit(GenerationRequest(prompt=p,
                                            max_new_tokens=max_new))
               for p in prompts]
    eng.run()
    wall = time.perf_counter() - t0
    assert all(eng.poll(h).state == "finished" for h in handles)

    st = eng.stats()
    ps = st["pool"]
    dense_peak = st["peak_dense_logical_bytes"]
    pinned_peak = max(1, ps["peak_referenced_bytes"])
    # per-sequence footprints at peak (all slots resident), used to
    # express the ratio as sequences-per-device at a fixed HBM budget
    budget = 1 << 20
    dense_per_seq = max(1, dense_peak // max_batch)
    comp_per_seq = max(1, pinned_peak // max_batch)
    rows.append({
        "name": "kv_concurrent_capacity",
        "us_per_call": wall * 1e6 / max(1, len(prompts)),
        "requests": len(prompts),
        "engine_slots": max_batch,
        "peak_dense_bytes": dense_peak,
        "peak_compressed_bytes": ps["peak_referenced_bytes"],
        "concurrent_capacity_ratio": round(dense_peak / pinned_peak, 4),
        "seqs_per_mib_dense": budget // dense_per_seq,
        "seqs_per_mib_compressed": budget // comp_per_seq,
        "dedup_hits": ps["dedup_hits"],
        "unique_blocks": ps["unique_blocks"],
        "ms_per_token_prefill": round(st["ms_per_token_prefill"], 2),
        "ms_per_token_decode": round(st["ms_per_token_decode"], 2),
    })

    # ---- sync vs prefetched (async) paging -------------------------------
    # The SAME request mix through two engines sharing one fixed-
    # geometry spec: host-driven sync paging (decode on the block-
    # boundary critical path) vs device-resident async paging (greedy
    # feedback on device over a window + DMA-prefetched block decodes
    # consumed one window later). Gated: the prefetched path may not be slower per decoded
    # token, and the trace-derived overlap fraction (decode time hidden
    # behind model compute / total decode wait) must stay majority-
    # hidden.
    fixed_spec = KVCacheSpec(block_tokens=4, mode="qlc", hot_blocks=1,
                             exact_capacity=False)

    def _drive(kv_paging):
        eng = Engine(params, cfg, max_seq_len=prompt_len + max_new + 4,
                     max_batch=max_batch, kv_spec=fixed_spec,
                     registry=CodecRegistry(), pool=BlockPool(1 << 30),
                     kv_paging=kv_paging)
        t0 = time.perf_counter()
        hs = [eng.submit(GenerationRequest(prompt=p,
                                           max_new_tokens=max_new))
              for p in prompts]
        eng.run()
        wall = time.perf_counter() - t0
        assert all(eng.poll(h).state == "finished" for h in hs)
        return eng, wall

    _drive("sync")      # warm the jit caches (the step fn and every
    _drive("async")     # window length this mix produces)
    eng_sync, _ = _drive("sync")
    eng_async, wall_async = _drive("async")
    st_s, st_a = eng_sync.stats(), eng_async.stats()
    for h_s, h_a in zip(
            (eng_sync.poll(h).tokens for h in
             [s.rid for s in eng_sync._seqs.values()]),
            (eng_async.poll(h).tokens for h in
             [s.rid for s in eng_async._seqs.values()])):
        np.testing.assert_array_equal(h_s, h_a)   # token identity
    sync_ms = st_s["ms_per_token_decode"]
    async_ms = st_a["ms_per_token_decode"]
    pf = st_a["prefetch"]
    rows.append({
        "name": "kv_prefetch_overlap",
        "us_per_call": wall_async * 1e6 / max(1, len(prompts)),
        "sync_ms_per_token": round(sync_ms, 3),
        "prefetched_ms_per_token": round(async_ms, 3),
        "prefetched_vs_sync_ratio": round(async_ms / max(sync_ms, 1e-9),
                                          4),
        "overlap_fraction": round(pf["overlap_fraction"], 4),
        "prefetch_scheduled": pf["scheduled"],
        "prefetch_hits": pf["hits"],
        "prefetch_stalled": pf["stalled"],
        "bytes_prefetched": pf["bytes_prefetched"],
        "windows": st_a["async"]["windows"],
        "d2h_per_window": st_a["async"]["d2h_per_window"],
    })
    return rows


if __name__ == "__main__":
    for row in run(n=1 << 15):
        row = dict(row)
        name = row.pop("name")
        us = row.pop("us_per_call")
        derived = ";".join(f"{k}={v}" for k, v in row.items())
        print(f"{name},{us:.1f},{derived}")
