"""Drive whole runs at a tiny size with the timed path broken
underneath, and see ``correct`` come out false; the sound run and the
lower-precision control bracket the limit."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.util import TINY_LIMIT

SEED = 2**33 + 17


def _run(root, cell, control=False):
    return harness.run_cell(harness.load_cell(root, cell), SEED, 3.0, False, 0.0,
                            control=control)


@pytest.mark.parametrize("cell", ["phi3-qlckv-chat", "glm3-qlckv-chat", "phi3-dense-chat"])
def test_sound_run_is_correct_and_control_is_not(tiny_root, cell):
    out = _run(tiny_root, cell, control=True)
    gap, limit = out["compared"]["logit_gap_max"]
    assert limit == TINY_LIMIT
    assert out["correct"] and gap <= limit
    assert out["compared"]["control_gap_max"][0] > limit


def _broken_step(alter):
    from repro.models import decode_step

    def factory(cfg):
        def step(p, tok, st, pos):
            lg, new = decode_step(p, cfg, tok, st, pos)
            nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)[:, None]
            return alter(cfg, nxt, st, new, pos)
        return jax.jit(step)
    return factory


def test_a_step_that_returns_its_state_unchanged_is_caught(tiny_root, monkeypatch):
    monkeypatch.setattr("repro.serving.scheduler._paged_step", _broken_step(
        lambda cfg, nxt, old, new, pos: (nxt, pos + 1, old)))
    assert not _run(tiny_root, "phi3-dense-chat")["correct"]


def test_a_token_altered_where_it_is_produced_is_caught(tiny_root, monkeypatch):
    monkeypatch.setattr("repro.serving.scheduler._paged_step", _broken_step(
        lambda cfg, nxt, old, new, pos: ((nxt + 1) % cfg.vocab_size, pos + 1, new)))
    assert not _run(tiny_root, "phi3-qlckv-chat")["correct"]


def test_a_block_restored_wrong_from_the_pool_is_caught(tiny_root, monkeypatch):
    from repro.models import attention
    restore = attention.kv_block_restore
    monkeypatch.setattr(attention, "kv_block_restore",
                        lambda cache, t0, t1, k, v: restore(cache, t0, t1, k, v * 0))
    assert not _run(tiny_root, "glm3-qlckv-chat")["correct"]
