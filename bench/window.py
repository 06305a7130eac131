"""What a measured window holds, and the end-to-end arithmetic on it.

Token times are the host clock at the return of the engine step that
delivered them; a request's submit time is when the harness handed it
to the engine. A token is in the window when its step returned inside
``(start, end]``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Record:
    """One request as the harness saw it."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    submit: float
    handle: str = ""
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    state: str = "waiting"

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.size)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of every sample, linearly interpolated
    between closest ranks (numpy's default)."""
    if len(values) == 0:
        raise ValueError("no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


def inside(t: float, start: float, end: float) -> bool:
    return start < t <= end


def output_tokens(records, start, end) -> int:
    return sum(1 for r in records for t in r.times if inside(t, start, end))


def ttfts(records, start, end) -> List[float]:
    """Seconds from submit to the step that returned the first token,
    of every request whose first token arrived in the window."""
    return [r.times[0] - r.submit for r in records
            if r.times and inside(r.times[0], start, end)]


def token_gaps(records, start, end) -> List[float]:
    """Seconds between consecutive tokens of a request, both in the
    window. Two tokens returned by one step (the prefill's and the
    first decode's) are 0 apart."""
    out = []
    for r in records:
        ts = [t for t in r.times if inside(t, start, end)]
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def end_to_end(records, start: float, end: float) -> dict:
    """The cell's end-to-end numbers over the window ``(start, end]``."""
    first, gaps = ttfts(records, start, end), token_gaps(records, start, end)
    return {
        "out_tok_s": output_tokens(records, start, end) / (end - start),
        "ttft_p50_ms": 1e3 * percentile(first, 50),
        "itl_p99_ms": 1e3 * percentile(gaps, 99),
        "n_ttft": len(first),
        "n_gaps": len(gaps),
    }


def served_flops(records, start, end, prefill_flops, token_flops) -> float:
    """Model FLOPs of the window: the prompt of every request admitted
    in it, and every token a decode step produced in it (token ``i`` of
    a request came from the step fed position ``P + i - 1``, which
    attends to ``P + i`` positions)."""
    total = 0.0
    for r in records:
        for i, t in enumerate(r.times):
            if not inside(t, start, end):
                continue
            total += (prefill_flops(r.prompt_len) if i == 0
                      else token_flops(r.prompt_len + i))
    return total
