"""Continuous-batching engine + shared block pool (PR 6).

The load-bearing claims, each pinned here:

* engine output is TOKEN-IDENTICAL to running every request alone
  through the scan oracle — continuous batching (join/leave mid-flight,
  queueing past ``max_batch``) must be a pure scheduling change;
* identical prompt prefixes dedup compressed blocks by container
  digest (prefix sharing), diverge copy-on-write, and the deduped
  bytes sit on the decode hot path (outputs stay exact);
* pool pressure degrades gracefully (LRU reclaim of zero-ref cache,
  spill to host) and exhaustion is a TYPED per-request rejection, never
  a crash of the neighbours;
* per-tenant fairness caps produce a deterministic admission trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.blockpool import BlockPool, PoolExhausted, container_digest
from repro.configs import get_config, reduced
from repro.core.registry import CodecRegistry
from repro.serving import Engine, GenerationRequest
from repro.serving.engine import ServeConfig, _generate_scanned
from repro.serving.kv_cache import KVCacheSpec

KEY = jax.random.PRNGKey(0)


def _model(arch):
    cfg = reduced(get_config(arch), frontend=None, frontend_prefix_len=0,
                  dtype="float32")
    return cfg, init_params_cached(arch, cfg)


_PARAMS = {}


def init_params_cached(arch, cfg):
    if arch not in _PARAMS:
        from repro.models import init_params
        _PARAMS[arch] = init_params(cfg, KEY)
    return _PARAMS[arch]


@pytest.fixture(scope="module", params=["phi3-mini-3.8b", "xlstm-125m"])
def setup(request):
    return _model(request.param)


@pytest.fixture(scope="module")
def phi3():
    return _model("phi3-mini-3.8b")


def _oracle(params, cfg, prompt, max_new):
    out = _generate_scanned(
        params, cfg, jnp.asarray(np.asarray(prompt, np.int32))[None],
        ServeConfig(max_seq_len=32, max_new_tokens=max_new))
    return list(np.asarray(out)[0])


def _prompts(cfg, lengths, seed=0, shared_prefix=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab_size, shared_prefix)
    return [np.concatenate(
        [pre, rng.integers(0, cfg.vocab_size, n - shared_prefix)])
        .astype(np.int32) for n in lengths]


# ---------------------------------------------------------------------------
# BlockPool unit behavior (no model, duck-typed blocks)
# ---------------------------------------------------------------------------

class _FakeBlock:
    def __init__(self, words, layer="l0", start=0):
        self.container = np.asarray(words, np.uint32)
        self.layer, self.start, self.tokens = layer, start, 4
        self.shapes, self.dtypes = ((4,),), ("f4",)

    @property
    def wire_bytes(self):
        return 4 * self.container.size


class TestBlockPool:
    def test_dedup_refcount_and_release(self):
        pool = BlockPool(1 << 20)
        a = _FakeBlock([1, 2, 3])
        d1 = pool.put(a)
        d2 = pool.put(_FakeBlock([1, 2, 3]))      # bit-identical -> dedup
        assert d1 == d2 and pool.refs(d1) == 2
        assert pool.stats()["dedup_hits"] == 1
        assert pool.stats()["logical_bytes"] == 2 * a.wire_bytes
        assert pool.stats()["resident_bytes"] == a.wire_bytes
        pool.release(d1)
        pool.release(d1)
        # zero-ref entries stay cached for later prefix hits ...
        assert d1 in pool and pool.refs(d1) == 0
        assert pool.stats()["referenced_bytes"] == 0
        # ... and revive on the next identical put
        assert pool.put(_FakeBlock([1, 2, 3])) == d1
        assert pool.refs(d1) == 1
        pool.release(d1)
        with pytest.raises(ValueError):
            pool.release(d1)            # double-release is a bug

    def test_geometry_salts_the_digest(self):
        pool = BlockPool(1 << 20)
        d1 = pool.put(_FakeBlock([7, 7], layer="l0", start=0))
        d2 = pool.put(_FakeBlock([7, 7], layer="l1", start=0))
        d3 = pool.put(_FakeBlock([7, 7], layer="l0", start=4))
        assert len({d1, d2, d3}) == 3
        assert container_digest([7, 7]) != container_digest([7, 8])

    def test_lru_reclaims_zero_ref_before_spilling(self):
        blk = _FakeBlock([0] * 25)                # 100 bytes each
        pool = BlockPool(250)
        d1 = pool.put(_FakeBlock([1] * 25))
        d2 = pool.put(_FakeBlock([2] * 25))
        pool.release(d1)                          # zero-ref cache
        pool.put(blk)                             # needs room: d1 drops
        st = pool.stats()
        assert d1 not in pool and d2 in pool
        assert st["reclaims"] == 1 and st["spills"] == 0
        # now only referenced entries remain: next put spills LRU (d2)
        pool.put(_FakeBlock([3] * 25))
        st = pool.stats()
        assert st["spills"] == 1 and st["host_bytes"] == 100
        # touching the spilled digest promotes it back (displacing the
        # LRU resident entry to host in its place) and counts the fetch
        pool.get(d2)
        st = pool.stats()
        assert st["host_fetches"] == 1 and st["spills"] == 2
        assert st["resident_bytes"] <= pool.capacity_bytes

    def test_exhaustion_is_typed(self):
        pool = BlockPool(250, spill_host=False)
        pool.put(_FakeBlock([1] * 25))
        pool.put(_FakeBlock([2] * 25))
        with pytest.raises(PoolExhausted):
            pool.put(_FakeBlock([3] * 25))        # all 200 bytes pinned
        with pytest.raises(PoolExhausted):
            BlockPool(50).put(_FakeBlock([1] * 25))   # single block > cap
        with pytest.raises(PoolExhausted):
            pool.check_admission(200)
        pool.check_admission(10)                  # fits next to pinned
        BlockPool(250).check_admission(10 ** 9)   # spill_host: no-op


# ---------------------------------------------------------------------------
# Engine == per-sequence oracle (the API-redesign contract)
# ---------------------------------------------------------------------------

class TestEngineOracle:
    def test_continuous_batching_token_identical(self, setup):
        """More requests than slots, mixed prompt/budget lengths: every
        request's tokens match running it ALONE through the oracle."""
        cfg, params = setup
        prompts = _prompts(cfg, [12, 9, 5, 7], seed=3)
        budgets = [4, 6, 3, 5]
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2)
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=m))
              for p, m in zip(prompts, budgets)]
        eng.run()
        for h, p, m in zip(hs, prompts, budgets):
            st = eng.poll(h)
            assert st.state == "finished"
            assert list(st.tokens) == _oracle(params, cfg, p, m), h
        assert eng.stats()["requests"]["finished"] == 4

    def test_compressed_paging_token_identical(self, setup):
        """Blocks round-trip through the codec + shared pool on the
        decode path and the outputs stay exact."""
        cfg, params = setup
        prompts = _prompts(cfg, [12, 10], seed=5)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry())
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=6))
              for p in prompts]
        eng.run()
        for h, p in zip(hs, prompts):
            assert list(eng.poll(h).tokens) == _oracle(params, cfg, p, 6)
        st = eng.stats()
        assert st["pool"]["unique_blocks"] > 0
        assert st["pool"]["logical_bytes"] == 0    # all refs released

    @pytest.mark.parametrize("new,per_step", [(8, [1, 1, 1, 1, 1, 0, 1]),
                                              (2, [4])])
    def test_sync_paging_codes_one_block_a_step(self, phi3, new, per_step):
        """A 17-token prompt's four blocks page out one a step after its
        admission, not all in the admission step; what is left when the
        request finishes pages then. Every completed block is pooled
        and the tokens stay exact."""
        cfg, params = phi3
        prompt = _prompts(cfg, [17], seed=7)[0]
        eng = Engine(params, cfg, max_seq_len=32, max_batch=1,
                     kv_spec=KVCacheSpec(block_tokens=4),
                     registry=CodecRegistry())
        h = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=new))
        paged = [0]
        while True:
            left = eng.step()
            paged.append(eng.stats()["kv"]["blocks_paged"])
            if not left:
                break
        assert np.diff(paged).tolist() == per_step
        assert paged[-1] == (17 + new - 1) // 4
        assert list(eng.poll(h).tokens) == _oracle(params, cfg, prompt, new)

    def test_deprecated_generate_matches_scan_oracle(self, setup):
        """The legacy batch call is now an Engine wrapper; it must stay
        bit-identical to the scan implementation it replaced."""
        from repro.serving import generate
        cfg, params = setup
        prompts = jnp.asarray(np.stack(_prompts(cfg, [8, 8], seed=7)))
        scfg = ServeConfig(max_seq_len=32, max_new_tokens=5)
        with pytest.warns(DeprecationWarning, match="Engine"):
            got = generate(params, cfg, prompts, scfg)
        want = _generate_scanned(params, cfg, prompts, scfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Prefix sharing / copy-on-write over the shared pool
# ---------------------------------------------------------------------------

class TestPrefixSharing:
    def test_identical_prompts_dedup_and_stay_exact(self, setup):
        """Two concurrent requests with IDENTICAL prompts produce
        bit-identical blocks (attention K/V slices AND cumulative SSM
        snapshots), so the pool holds each block once with refcount 2 —
        and both outputs still match the oracle."""
        cfg, params = setup
        prompts = _prompts(cfg, [12, 12], seed=9, shared_prefix=12)
        pool = BlockPool(1 << 30)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry(), pool=pool)
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=6))
              for p in prompts]
        eng.run()
        for h, p in zip(hs, prompts):
            assert list(eng.poll(h).tokens) == _oracle(params, cfg, p, 6)
        st = pool.stats()
        n_layers = len(cfg.layer_kinds())
        assert st["dedup_hits"] >= n_layers
        assert st["peak_logical_bytes"] > st["peak_referenced_bytes"]

    def test_divergent_suffix_is_copy_on_write(self, phi3):
        """Prompts sharing an 8-token prefix but diverging in the last
        block: attention K/V rows are position-local, so the prefix
        blocks dedup while the divergent blocks get NEW digests (no
        false sharing — outputs stay exact). SSM states are cumulative,
        so this attention-only property is tested on phi3."""
        cfg, params = phi3
        prompts = _prompts(cfg, [12, 12], seed=9, shared_prefix=12)
        prompts[1][-4:] = (prompts[1][-4:] + 1) % cfg.vocab_size  # diverge
        pool = BlockPool(1 << 30)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry(), pool=pool)
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=6))
              for p in prompts]
        eng.run()
        for h, p in zip(hs, prompts):
            assert list(eng.poll(h).tokens) == _oracle(params, cfg, p, 6)
        st = pool.stats()
        n_layers = len(cfg.layer_kinds())
        # [0,4) and [4,8) dedup per layer; [8,12) and the decode-time
        # blocks diverge copy-on-write
        assert st["dedup_hits"] >= 2 * n_layers
        assert st["unique_blocks"] > st["dedup_hits"]

    def test_finished_sequence_leaves_prefix_cache(self, phi3):
        """A finished request's blocks stay as zero-ref cache; a later
        identical-prefix request revives them (dedup against cache) and
        still decodes exactly."""
        cfg, params = phi3
        prompts = _prompts(cfg, [12, 12], seed=11, shared_prefix=12)
        pool = BlockPool(1 << 30)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=1,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry(), pool=pool)
        h1 = eng.submit(GenerationRequest(prompt=prompts[0],
                                          max_new_tokens=3))
        eng.run()                                  # finishes, refs -> 0
        assert pool.stats()["referenced_bytes"] == 0
        hits_before = pool.stats()["dedup_hits"]
        h2 = eng.submit(GenerationRequest(prompt=prompts[1],
                                          max_new_tokens=3))
        eng.run()
        assert pool.stats()["dedup_hits"] > hits_before
        for h, p in zip((h1, h2), prompts):
            assert list(eng.poll(h).tokens) == _oracle(params, cfg, p, 3)


# ---------------------------------------------------------------------------
# Pressure: spill, reclaim, typed rejection
# ---------------------------------------------------------------------------

class TestPoolPressure:
    def test_spill_keeps_outputs_exact(self, phi3):
        """A pool far smaller than the working set spills to host; the
        device tier never exceeds capacity and outputs stay exact."""
        cfg, params = phi3
        prompts = _prompts(cfg, [12, 12, 12], seed=13)
        # blocks are ~4.25 KB here; the 2-resident working set peaks at
        # ~21 KB, so 10 KB holds any one block but not the working set
        pool = BlockPool(10_000)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry(), pool=pool)
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=5))
              for p in prompts]
        eng.run()
        st = pool.stats()
        assert st["spills"] + st["reclaims"] > 0
        assert st["peak_resident_bytes"] <= pool.capacity_bytes
        for h, p in zip(hs, prompts):
            assert list(eng.poll(h).tokens) == _oracle(params, cfg, p, 5)

    def test_exhaustion_rejects_one_request_not_the_engine(self, phi3):
        """With spill disabled and capacity for roughly one sequence,
        the overflowing request gets a typed rejection; its neighbour
        runs to completion untouched."""
        cfg, params = phi3
        prompts = _prompts(cfg, [12, 12], seed=15)
        # 15 KB pins one sequence's ~12.8 KB of blocks; the second
        # request's projection cannot fit beside it
        pool = BlockPool(15_000, spill_host=False)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     kv_spec=KVCacheSpec(block_tokens=4, hot_blocks=1),
                     registry=CodecRegistry(), pool=pool)
        hs = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=5))
              for p in prompts]
        eng.run()                                  # must not raise
        states = [eng.poll(h) for h in hs]
        assert states[0].state == "finished"
        assert list(states[0].tokens) == _oracle(params, cfg,
                                                 prompts[0], 5)
        assert states[1].state == "rejected"
        assert "PoolExhausted" in states[1].error
        ev = [e for _, e, _ in eng.events]
        assert "reject" in ev or "reject_admission" in ev


# ---------------------------------------------------------------------------
# Fairness
# ---------------------------------------------------------------------------

class TestFairness:
    def test_tenant_cap_defers_deterministically(self, phi3):
        """fairness_cap=0.5 of max_batch=2 -> one slot per tenant: the
        second request of tenant A defers while tenant B's first request
        takes the free slot; A's second runs once A's first finishes."""
        cfg, params = phi3
        p = _prompts(cfg, [6, 6, 6], seed=17)
        eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                     fairness_cap=0.5)
        eng.submit(GenerationRequest(prompt=p[0], max_new_tokens=2,
                                     tenant="A", request_id="A1"))
        eng.submit(GenerationRequest(prompt=p[1], max_new_tokens=2,
                                     tenant="A", request_id="A2"))
        eng.submit(GenerationRequest(prompt=p[2], max_new_tokens=3,
                                     tenant="B", request_id="B1"))
        eng.run()
        assert (1, "admit", "A1") in eng.events
        assert (1, "defer_fairness", "A2") in eng.events
        assert (1, "admit", "B1") in eng.events
        a2_admit = [s for s, e, r in eng.events
                    if e == "admit" and r == "A2"]
        a1_finish = [s for s, e, r in eng.events
                     if e == "finish" and r == "A1"]
        assert a2_admit and a1_finish and a2_admit[0] > a1_finish[0]
        assert eng.stats()["requests"]["finished"] == 3
        # identity still holds under deferred admission
        assert list(eng.poll("A2").tokens) == _oracle(params, cfg,
                                                      p[1], 2)
