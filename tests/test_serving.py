"""Serving engine: prefill consistency (one pass against the token
scan), batched generation, the engine's prefill counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import forward, init_decode_states, init_params
from repro.serving import (Engine, GenerationRequest, ServeConfig, generate,
                           prefill)
from repro.serving.engine import prefill_mode, prefill_serial

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module", params=["phi3-mini-3.8b", "xlstm-125m"])
def setup(request):
    cfg = reduced(get_config(request.param), frontend=None,
                  frontend_prefix_len=0, dtype="float32")
    params = init_params(cfg, KEY)
    return cfg, params


class TestPrefill:
    def test_prefill_matches_forward_last_logits(self, setup):
        cfg, params = setup
        b, s = 2, 12
        tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
        states = init_decode_states(cfg, b, 32)
        last, _ = prefill(params, cfg, tokens, states)
        full = forward(params, cfg, tokens)
        np.testing.assert_allclose(
            np.asarray(last), np.asarray(full[:, -1]), rtol=2e-3, atol=2e-3)


class TestGenerate:
    def test_shapes_and_determinism(self, setup):
        cfg, params = setup
        sc = ServeConfig(max_seq_len=48, max_new_tokens=8)
        prompts = jax.random.randint(KEY, (3, 10), 0, cfg.vocab_size)
        out1 = generate(params, cfg, prompts, sc)
        out2 = generate(params, cfg, prompts, sc)
        assert out1.shape == (3, 8)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        assert (np.asarray(out1) < cfg.vocab_size).all()

    def test_greedy_continuation_consistency(self, setup):
        """Generating t tokens then continuing == generating t+k direct.

        Greedy decode is deterministic, so prefill(prompt + first gen
        tokens) must produce the same continuation."""
        cfg, params = setup
        sc_long = ServeConfig(max_seq_len=64, max_new_tokens=6)
        prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
        full = np.asarray(generate(params, cfg, prompts, sc_long))
        ext = jnp.concatenate([prompts, jnp.asarray(full[:, :3])], axis=1)
        sc_short = ServeConfig(max_seq_len=64, max_new_tokens=3)
        cont = np.asarray(generate(params, cfg, ext, sc_short))
        np.testing.assert_array_equal(cont, full[:, 3:])


ONE_PASS = {"phi3-mini-3.8b": "bulk", "chatglm3-6b": "bulk",
            "mixtral-8x22b": "serial", "jamba-1.5-large-398b": "serial"}


@pytest.fixture(scope="module", params=sorted(ONE_PASS))
def any_kind(request):
    cfg = reduced(get_config(request.param), frontend=None,
                  frontend_prefix_len=0, dtype="float32")
    return request.param, cfg, init_params(cfg, KEY)


def _token_scans(fn, *args, length):
    """Number of ``scan`` equations of ``length`` steps in fn's jaxpr."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and eqn.params["length"] == length:
                n += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


class TestOnePassPrefill:
    """``prefill`` runs attention-only, dense-FFN configurations in one
    forward pass and matches the token scan ``prefill_serial``."""

    @pytest.mark.parametrize("start", [0, 5])
    def test_matches_the_token_scan(self, any_kind, start):
        name, cfg, params = any_kind
        b, s = 2, 11
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, start + s),
                                    0, cfg.vocab_size)
        states = init_decode_states(cfg, b, 32)
        if start:       # a partly filled cache: its length is the start
            _, states = prefill_serial(params, cfg, tokens[:, :start], states)
        want, want_st = prefill_serial(params, cfg, tokens[:, start:], states,
                                       start_pos=start)
        got, got_st = prefill(params, cfg, tokens[:, start:], states,
                              start_pos=start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        for g, w in zip(jax.tree.leaves(got_st), jax.tree.leaves(want_st),
                        strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)

    def test_mode_follows_the_layers(self, any_kind):
        name, cfg, params = any_kind
        assert prefill_mode(cfg) == ONE_PASS[name]
        s = 11
        tokens = jnp.zeros((1, s), jnp.int32)
        states = init_decode_states(cfg, 1, 16)
        scans = _token_scans(lambda t, st: prefill(params, cfg, t, st),
                             tokens, states, length=s)
        assert (scans == 0) == (ONE_PASS[name] == "bulk")
        assert _token_scans(lambda t, st: prefill_serial(params, cfg, t, st),
                            tokens, states, length=s) >= 1


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "jamba-1.5-large-398b"])
def test_engine_counts_bulk_prefill_tokens(arch):
    cfg = reduced(get_config(arch), frontend=None, frontend_prefix_len=0,
                  dtype="float32")
    eng = Engine(init_params(cfg, KEY), cfg, max_seq_len=32, max_batch=2)
    rng = np.random.default_rng(5)
    for n in (7, 4, 9):
        eng.submit(GenerationRequest(prompt=rng.integers(0, cfg.vocab_size, n),
                                     max_new_tokens=3))
    eng.run()
    st = eng.stats()
    assert st["prefill_tokens"] == 20
    assert st["prefill_bulk_tokens"] == (20 if ONE_PASS[arch] == "bulk" else 0)
