import jax
import numpy as np
import pytest

from bench import families
from bench.tests.util import tiny_config
from bench.weights import base_key

DENSE = families.module("dense")


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "chatglm3-6b", "phi3-mini-3.8b-densekv"])
def test_program_layout_holds_the_references_values_bit_for_bit(name):
    c = tiny_config(name)
    seed = 2**31 + 5
    params = DENSE.program_params(c, seed)
    key = base_key(seed)
    for layer in range(c["num_hidden_layers"]):
        kind = DENSE.layer_kind(c, layer)
        want = DENSE.to_program_layer(c, DENSE.layer_weights(c, key, layer, kind))
        got = jax.tree.map(lambda a: a[layer], params["groups"]["l0"])
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16)), got, want)
    o = DENSE.outer_weights(c, key)
    np.testing.assert_array_equal(np.asarray(params["head"]), np.asarray(o["lm_head"]))


def test_seeds_past_32_bits_differ():
    c = tiny_config("phi3-mini-3.8b")
    a = DENSE.layer_weights(c, base_key(5), 0, DENSE.KIND)["q"]
    b = DENSE.layer_weights(c, base_key(5 + 2**31), 0, DENSE.KIND)["q"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        base_key(-1)


def test_rope_permutation_pairs_halves_into_adjacent_slots():
    c = dict(head_dim=8, partial_rotary_factor=1.0, rope_pairs="halves")
    assert DENSE.rope_perm(c).tolist() == [0, 4, 1, 5, 2, 6, 3, 7]
    c.update(rope_pairs="adjacent", partial_rotary_factor=0.5)
    assert DENSE.rope_perm(c).tolist() == list(range(8))
