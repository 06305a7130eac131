"""Device-resident async KV paging: device framing parity, prefetch
decode bit-identity, the eviction-under-prefetch race, the jitted
window step's zero-host-transfer contract, and SSM prefix sharing.

The async path's contract is the sync path's, minus the host: a block
framed by ``encode_block_device`` is BIT-identical to the sync
``encode_block_arrays`` container (same digests — that identity is
what lets sync and async engines share one block pool), and every
decode route (device plan decode, prefetch-kernel stream decode) is
bit-identical to ``decode_block_arrays``. Races never return stale
data: an arena slot freed between schedule and consume surfaces a
typed :class:`ArenaStale`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.calibrate import byte_planes, kv_symbol_stream
from repro.configs import get_config, reduced
from repro.core.registry import CodecRegistry
from repro.models import init_decode_states, init_params
from repro.serving import (ArenaStale, BlockArena, KVCacheSpec,
                           PagedKVCache, ServeConfig, calibrate_cache,
                           prefill)
from repro.serving.engine import _decode_window, _generate_scanned
from repro.serving.kv_cache import (calibration_arrays,
                                    device_byte_planes,
                                    device_symbol_stream)
from repro.serving.scheduler import Engine, GenerationRequest

KEY = jax.random.PRNGKey(0)
ARCHS = ["phi3-mini-3.8b", "xlstm-125m"]


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    cfg = reduced(get_config(request.param), frontend=None,
                  frontend_prefix_len=0)
    params = init_params(cfg, KEY)
    prompts = jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)
    states = init_decode_states(cfg, 2, 64)
    _, states = prefill(params, cfg, prompts, states)
    return cfg, params, jax.block_until_ready(states)


def _cache(cfg, states, use_kernels=False, **spec_kw):
    reg = CodecRegistry()
    spec_kw.setdefault("exact_capacity", False)
    spec = KVCacheSpec(block_tokens=4, mode="qlc",
                       use_kernels=use_kernels, **spec_kw)
    calibrate_cache(reg, cfg, states, 12, spec)
    return PagedKVCache(spec, cfg, reg), reg


def _shared_prefix_prompts(cfg, n=3, length=10, shared=8):
    out = [np.array(jax.random.randint(jax.random.PRNGKey(i), (length,),
                                       0, cfg.vocab_size))
           for i in range(n)]
    for p in out[2:]:
        p[:shared] = out[1][:shared]
    return out


class TestDeviceFraming:
    def test_device_planes_match_host(self, setup):
        """The bitcast byte planes / symbol stream are bit-identical to
        the numpy-view host versions — the precondition for digest
        parity."""
        cfg, _, states = setup
        arrays = calibration_arrays(cfg, states, 4)["l0"]
        host = byte_planes(arrays)
        dev = device_byte_planes(arrays)
        assert set(host) == set(dev)
        for k in host:
            np.testing.assert_array_equal(np.asarray(host[k]),
                                          np.asarray(dev[k]))
        np.testing.assert_array_equal(
            np.asarray(kv_symbol_stream(arrays, "qlc")),
            np.asarray(device_symbol_stream(arrays)))

    def test_device_frame_matches_sync_container(self, setup):
        """Digest parity: the device-framed words equal the sync host
        container byte-for-byte for every layer, and the static-offset
        device decode round-trips exactly."""
        cfg, _, states = setup
        cache, _ = _cache(cfg, states)
        arrays = calibration_arrays(cfg, states, 4)
        for i in range(len(cfg.layer_kinds())):
            key = f"l{i}"
            name = cache.spec.layer_codec(i)
            host = cache.encode_block_arrays(name, key, arrays[key],
                                             start=0, tokens=4)
            dev = cache.encode_block_device(name, key, arrays[key],
                                            start=0, tokens=4)
            assert dev is not None
            np.testing.assert_array_equal(host.container,
                                          np.asarray(dev.words))
            assert dev.coded == host.coded
            decoded, oks = cache.decode_block_device(dev.plan, dev.words)
            for orig, got in zip(arrays[key], decoded):
                assert str(np.asarray(orig).dtype) == str(got.dtype)
                np.testing.assert_array_equal(
                    np.asarray(orig).view(np.uint8),
                    np.asarray(got).view(np.uint8))
            for ok in oks:
                assert bool(ok)

    @pytest.mark.parametrize("use_kernels", [False, True],
                             ids=["pure", "fused"])
    def test_prefetch_decode_bit_identical(self, setup, use_kernels):
        """``decode_block_arrays_async`` (DMA prefetch kernel) equals
        ``decode_block_arrays`` bit-for-bit on the same container, for
        both container decode paths and every layer kind."""
        cfg, _, states = setup
        cache, _ = _cache(cfg, states, use_kernels=use_kernels)
        arrays = calibration_arrays(cfg, states, 4)
        for i in range(len(cfg.layer_kinds())):
            key = f"l{i}"
            block = cache.encode_block_arrays(
                cache.spec.layer_codec(i), key, arrays[key],
                start=0, tokens=4)
            sync = cache.decode_block_arrays(block)
            pref = cache.decode_block_arrays_async(block)
            for a, b in zip(sync, pref):
                np.testing.assert_array_equal(
                    np.asarray(a).view(np.uint8),
                    np.asarray(b).view(np.uint8))

    def test_frame_plan_requires_fixed_geometry(self, setup):
        cfg, _, states = setup
        cache, _ = _cache(cfg, states, exact_capacity=True)
        with pytest.raises(ValueError, match="exact_capacity"):
            cache.frame_plan(cache.spec.layer_codec(0), ((2, 4),),
                             ("float32",))


class TestPrefetchRace:
    def test_eviction_under_prefetch_raises_stale(self, setup):
        """A block evicted from the arena between schedule and consume
        surfaces a typed ``ArenaStale`` — never stale data."""
        cfg, _, states = setup
        cache, _ = _cache(cfg, states)
        arrays = calibration_arrays(cfg, states, 4)["l0"]
        name = cache.spec.layer_codec(0)
        dev = cache.encode_block_device(name, "l0", arrays,
                                        start=0, tokens=4)
        arena = BlockArena(2, int(dev.words.shape[0]))
        cache.arena = arena
        slot, gen = arena.alloc()
        arena.write(slot, dev.words)
        dev.slot, dev.gen = slot, gen
        handle = cache.prefetcher.schedule(dev)
        arena.free(slot)                 # the race: reclaim in between
        with pytest.raises(ArenaStale):
            cache.prefetcher.consume(handle)
        assert arena.stale_reads >= 1

    def test_consume_counts_hit(self, setup):
        cfg, _, states = setup
        cache, _ = _cache(cfg, states)
        arrays = calibration_arrays(cfg, states, 4)["l0"]
        dev = cache.encode_block_device(cache.spec.layer_codec(0), "l0",
                                        arrays, start=0, tokens=4)
        handle = cache.prefetcher.schedule(dev)
        jax.block_until_ready(handle.arrays)
        out = cache.prefetcher.consume(handle)
        assert cache.prefetcher.hits == 1
        assert cache.stats()["prefetch"]["scheduled"] == 1
        for orig, got in zip(arrays, out):
            np.testing.assert_array_equal(
                np.asarray(orig).view(np.uint8),
                np.asarray(got).view(np.uint8))


class TestAsyncEngine:
    def test_async_requires_qlc_fixed_geometry(self, setup):
        cfg, params, _ = setup
        for spec in (None,
                     KVCacheSpec(mode="e4m3", exact_capacity=False),
                     KVCacheSpec(mode="qlc", exact_capacity=True)):
            with pytest.raises(ValueError, match="async"):
                Engine(params, cfg, max_seq_len=64, kv_spec=spec,
                       kv_paging="async")
        with pytest.raises(ValueError, match="kv_paging"):
            Engine(params, cfg, max_seq_len=64, kv_paging="weird")

    def test_token_identity_and_prefix_sharing(self, setup):
        """The async engine is token-identical to the dense oracle AND
        the sync engine over a shared-prefix mix; shared prompt-prefix
        blocks dedup in the pool for BOTH layer architectures (SSM via
        boundary-state re-basing), and the jitted window loop does its
        constant 2-up/1-down host transfers per window."""
        cfg, params, _ = setup
        prompts = _shared_prefix_prompts(cfg)
        new = 10

        oracle = [np.asarray(_generate_scanned(
            params, cfg, jnp.asarray(p[None, :]),
            ServeConfig(max_seq_len=64, max_new_tokens=new)))[0]
            for p in prompts]

        spec = KVCacheSpec(block_tokens=4, mode="qlc",
                           exact_capacity=False)

        def drive(kv_paging):
            eng = Engine(params, cfg, max_seq_len=64, max_batch=4,
                         kv_spec=spec, kv_paging=kv_paging)
            hs = [eng.submit(GenerationRequest(prompt=p,
                                               max_new_tokens=new))
                  for p in prompts]
            eng.run()
            return eng, [eng.poll(h).tokens for h in hs]

        eng_sync, sync_toks = drive("sync")
        eng_async, async_toks = drive("async")
        for o, s, a in zip(oracle, sync_toks, async_toks):
            np.testing.assert_array_equal(o, s)
            np.testing.assert_array_equal(o, a)

        # prefix sharing fires on both paths (SSM layers via re-basing)
        assert eng_sync.stats()["pool"]["dedup_hits"] > 0
        st = eng_async.stats()
        assert st["pool"]["dedup_hits"] > 0
        # window transfer contract + measured prefetch overlap
        assert st["async"]["windows"] >= 1
        assert st["async"]["h2d_per_window"] == 2.0
        assert st["async"]["d2h_per_window"] == 1.0
        pf = st["prefetch"]
        assert pf["scheduled"] > 0
        assert pf["hits"] + pf["stalled"] == pf["scheduled"]
        assert pf["bytes_prefetched"] > 0

    def test_window_step_disallows_host_transfers(self, setup):
        """The probe behind the engine's counters: a whole 8-token
        window dispatches under ``jax.transfer_guard("disallow")`` —
        any per-token host callback or implicit transfer inside the
        window would raise."""
        cfg, params, _ = setup
        states = init_decode_states(cfg, 2, 64)
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2, 1), jnp.int32)
        with jax.transfer_guard("disallow"):
            toks, states = _decode_window(cfg, params, tok, pos, states, 8)
        assert np.asarray(toks).shape == (2, 8)
