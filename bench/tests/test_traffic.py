import json

import numpy as np
import pytest

from bench import traffic
from bench.tests.util import ROOT

MIX = traffic.load(ROOT / "bench" / "traffic" / "chat-closed8.json")


def _take(seed, n=64):
    s = traffic.stream(MIX, seed, 32064)
    return [next(s) for _ in range(n)]


def test_same_seed_same_requests():
    a, b = _take(2**31 + 7), _take(2**31 + 7)
    assert [(r.prompt.tolist(), r.max_new_tokens) for r in a] == \
        [(r.prompt.tolist(), r.max_new_tokens) for r in b]


def test_other_seed_other_tokens_same_lengths():
    a, b = _take(5), _take(2**31 + 6)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    assert [(r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.prompt.size, r.max_new_tokens) for r in b]
    # each deck serves every stratified length once
    n, k = MIX["deck"], MIX["clients"]
    for d in range(3):
        sl = slice(k + d * n, k + (d + 1) * n)
        assert sorted(r.prompt.size for r in a[sl]) == traffic.deck(MIX["prompt"], n)
        assert sorted(r.max_new_tokens for r in a[sl]) == traffic.deck(MIX["output"], n)


def test_lengths_only_from_buckets_and_clips():
    reqs = _take(2**40 + 3, 400)
    out = MIX["output"]
    assert {r.prompt.size for r in reqs} <= set(MIX["prompt"]["buckets"])
    assert all(out["min"] <= r.max_new_tokens <= out["max"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32064 for r in reqs)
    assert [[r.prompt.size, r.max_new_tokens] for r in reqs[:MIX["clients"]]] == MIX["prime"]


def test_deck_follows_the_distribution():
    prompts = traffic.deck(MIX["prompt"], 16)
    assert prompts == sorted(prompts) and prompts[-1] == 512
    assert 240 <= np.mean(prompts) <= 290            # the mix's mean, ~266
    assert traffic.shape_length(MIX["prompt"], 161) == 256   # median lands in 256
    assert traffic.shape_length(MIX["prompt"], 900) == 512   # clipped at 512
    assert traffic.shape_length(MIX["output"], 5000) == 448


@pytest.mark.parametrize("change,words", [
    ({"prime": [[512, 64]]}, "prime"),
    ({"prime": [[100, 64]] * 8}, "prime"),
    ({"prompt": dict(MIX["prompt"], order=[0] * MIX["deck"])}, "order"),
])
def test_mix_file_is_validated(tmp_path, change, words):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(dict(MIX, **change)))
    with pytest.raises(ValueError, match=words):
        traffic.load(p)
