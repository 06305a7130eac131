import json

import numpy as np
import pytest

from bench import roofline, window
from bench.harness import Clients, metric_reader
from bench.tests.util import ROOT
from bench.window import Record

PHI3 = json.loads((ROOT / "bench" / "configs" / "phi3-mini-3.8b.json").read_text())
PEAKS = roofline.peaks("TPU v5 lite")


class FakeEngine:
    """submit/step/poll of repro.serving.Engine: every step admits what
    waits (slots permitting) and gives each running request a token."""

    def __init__(self, slots):
        from repro.serving.scheduler import RequestStatus
        self.status, self.slots = RequestStatus, slots
        self.reqs, self.running = {}, []

    def submit(self, req):
        self.reqs[req.request_id] = [req, []]
        return req.request_id

    def step(self):
        waiting = [r for r in self.reqs if r not in self.running and not self._done(r)]
        for rid in waiting[:self.slots - len(self.running)]:
            self.running.append(rid)
        for rid in list(self.running):
            self.reqs[rid][1].append(7)
            if self._done(rid):
                self.running.remove(rid)

    def _done(self, rid):
        req, toks = self.reqs[rid]
        return len(toks) >= req.max_new_tokens

    def poll(self, rid):
        req, toks = self.reqs[rid]
        return self.status(rid, "default", "finished" if self._done(rid) else "running",
                           np.asarray(toks, np.int32))


def test_closed_loop_keeps_every_client_in_flight():
    from bench import traffic
    mix = traffic.load(ROOT / "bench" / "traffic" / "chat-closed8.json")
    eng = FakeEngine(slots=8)
    loop = Clients(eng, traffic.stream(mix, 1, 100), mix["clients"])
    for _ in range(400):
        loop.submit_idle()
        assert len(eng.running) + sum(
            1 for r in eng.reqs if r not in eng.running and not eng._done(r)) == 8
        loop.step()
    done = [r for r in loop.records if r.state == "finished"]
    assert len(done) > 8


def _rec(submit, times, prompt=4):
    r = Record(0, np.zeros(prompt, np.int32), len(times), submit)
    r.times = list(times)
    return r


def test_percentiles_are_over_every_sample_of_the_window():
    recs = [_rec(0.5, [1.0, 1.0, 1.1, 1.3, 2.9]), _rec(1.05, [1.2, 1.25, 3.5]),
            _rec(0.0, [0.5, 0.9, 1.4])]          # first token before the window
    start, end = 0.95, 3.0
    e2e = window.end_to_end(recs, start, end)
    gaps = [0.0, 0.1, 0.2, 1.6, 0.05]
    assert e2e["n_gaps"] == len(gaps) and e2e["n_ttft"] == 2
    assert e2e["itl_p99_ms"] == pytest.approx(1e3 * np.percentile(gaps, 99))
    assert e2e["ttft_p50_ms"] == pytest.approx(1e3 * np.median([0.5, 0.15]))
    assert e2e["out_tok_s"] == pytest.approx(8 / 2.05)     # 3.5 is after the end


def test_metric_arithmetic_on_a_recorded_stats_diff():
    rec = json.loads((ROOT / "bench" / "tests" / "data" / "stats_diff.json").read_text())
    s0, s1 = rec["stats0"], rec["stats1"]
    run = dict(stats0=s0, stats1=s1)
    want = ((s1["ms_per_token_prefill"] * s1["prefill_tokens"]
             - s0["ms_per_token_prefill"] * s0["prefill_tokens"])
            / (s1["prefill_tokens"] - s0["prefill_tokens"]))
    assert metric_reader(ROOT, "prefill_ms_per_tok")(run) == pytest.approx(want)
    steps = [{"wall_s": 0.5, "prefill_s": 0.3, "decode_s": 0.1},
             {"wall_s": 0.1, "prefill_s": 0.0, "decode_s": 0.08}]
    assert metric_reader(ROOT, "step_other_ms")(dict(steps=steps)) == pytest.approx(60.0)
    assert metric_reader(ROOT, "prefill_ms_per_tok")(dict(stats0=s1, stats1=s1)) is None


def test_serve_mfu_counts_prompts_and_decoded_tokens():
    recs = [_rec(0.0, [1.0, 2.0, 3.0], prompt=100)]
    run = dict(records=recs, start=0.5, end=3.0, config=PHI3, peaks=PEAKS)
    flops = (roofline.prefill_flops(PHI3, 100) + roofline.token_flops(PHI3, 101)
             + roofline.token_flops(PHI3, 102))
    assert metric_reader(ROOT, "serve_mfu")(run) == pytest.approx(
        100 * flops / (2.5 * 197e12))
    assert metric_reader(ROOT, "serve_mfu")(dict(run, peaks=None)) is None


def test_roofline_counts():
    # phi3-mini-3.8b: 3,821,079,552 parameters; the matmuls skip the embedding
    assert roofline.matmul_params(PHI3) == 3821079552 - 32064 * 3072 - 65 * 3072
    assert roofline.kv_bytes_per_token(PHI3) == 393216
    assert roofline.prefill_flops(PHI3, 3) == sum(
        roofline.token_flops(PHI3, p) for p in (1, 2, 3))
    assert roofline.kv_codec_bytes(PHI3, 128) == 2 * 128 * 393216
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_trace_metrics_read_nothing_without_their_work():
    empty = dict(trace={"executables": {}, "span_busy_s": {}, "busy_s": 1.0,
                        "window_s": 4.0}, counts={}, peaks=PEAKS, config=PHI3,
                 settings={"kv_block": 128})
    assert metric_reader(ROOT, "decode_step_ms")(empty) is None
    assert metric_reader(ROOT, "kv_codec_roofline")(empty) is None
    assert metric_reader(ROOT, "idle_share")(empty) == pytest.approx(75.0)
    busy = dict(empty, counts={"page": 2}, trace=dict(empty["trace"],
                span_busy_s={"page": 0.5}, executables={"jit_step": [4, 0.1]}))
    assert metric_reader(ROOT, "decode_step_ms")(busy) == pytest.approx(25.0)
    assert metric_reader(ROOT, "kv_codec_roofline")(busy) == pytest.approx(
        100 * 4 * 128 * 393216 / 819e9 / 0.5)
