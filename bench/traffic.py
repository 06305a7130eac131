"""One general generator for every traffic mix in ``bench/traffic``.

A mix is a JSON file of parameters. Lengths are drawn from the named
distribution by stratified sampling: a *deck* of ``deck`` lengths sits
at the distribution's quantiles ``(i + 0.5) / deck`` (rounded up to a
bucket, clipped), and the requests take them deck after deck in the
fixed ``order`` the file gives (indices into the deck, low and high
quantiles interleaved). The seed draws the token ids. So every seed
serves the same lengths in the same order: a window a few admissions
long holds the same work whatever the seed, and two seeds differ in
content, not in how much work a run holds.

The stream opens with ``prime`` requests, one per client, whose
``[prompt, output]`` lengths are listed in the file: they are admitted
during set-up (one of each prompt bucket, so every prefill length
compiles there) and are in flight when the measured window opens.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def load(path) -> dict:
    mix = json.loads(Path(path).read_text())
    for key in ("loop", "clients", "deck", "prompt", "output", "prime"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: loop {mix['loop']!r} is not supported")
    if len(mix["prime"]) != mix["clients"]:
        raise ValueError(f"{path}: one prime request per client")
    for p, o in mix["prime"]:
        if p not in mix["prompt"]["buckets"] or shape_length(mix["output"], o) != o:
            raise ValueError(f"{path}: prime request {[p, o]} is off the mix's lengths")
    for key in ("prompt", "output"):
        if sorted(mix[key]["order"]) != list(range(mix["deck"])):
            raise ValueError(f"{path}: {key} order is not a permutation of the deck")
    return mix


def _quantile(spec: dict, q: float) -> float:
    if spec["dist"] != "lognormal":
        raise ValueError(f"distribution {spec['dist']!r}")
    return spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(q))


def shape_length(spec: dict, x: float) -> int:
    """A drawn length as served: rounded up to the first bucket that
    holds it (the last bucket if none does), else clipped."""
    if "buckets" in spec:
        for b in spec["buckets"]:
            if x <= b:
                return int(b)
        return int(spec["buckets"][-1])
    return int(min(max(math.ceil(x), spec["min"]), spec["max"]))


def deck(spec: dict, n: int) -> List[int]:
    """The ``n`` stratified lengths of one deck, in quantile order."""
    return [shape_length(spec, _quantile(spec, (i + 0.5) / n)) for i in range(n)]


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The requests of a run, in the order clients take them: first the
    ``prime`` requests, then deck after deck in the file's order."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    n = mix["deck"]
    prompts, outputs = deck(mix["prompt"], n), deck(mix["output"], n)
    lengths = [(p, o) for p, o in mix["prime"]]
    for idx in itertools.count():
        if idx >= len(lengths):
            lengths += [(prompts[i], outputs[j]) for i, j in
                        zip(mix["prompt"]["order"], mix["output"]["order"])]
        p, o = lengths[idx]
        yield Request(idx, rng.integers(0, vocab, p, dtype=np.int64).astype(np.int32),
                      int(o))
