import shutil
import types

import pytest

from bench import spans
from bench import trace_reduce as tr
from bench.harness import metric_reader
from bench.tests.util import ROOT

DATA = ROOT / "bench" / "tests" / "data"
SPANS_TRACE = DATA / "spans.xplane.pb"      # record_spans.py
HARNESS_TRACE = DATA / "cpu.xplane.pb"      # record_trace.py: no program span
NAMES = {"engine.step", "engine.admit", "engine.prefill", "engine.slot_write",
         "engine.decode", "engine.page", "kv.encode", "pool.put", "kv.decode",
         "kv.restore"}


@pytest.fixture(scope="module")
def att():
    return spans.attribute(tr.read_events(SPANS_TRACE))


def test_flatten_partitions_by_the_innermost_span():
    got = spans._flatten([("a", 1, 9), ("b", 2, 4), ("c", 3, 4), ("d", 6, 12)], 0, 10)
    # d outlasts a, its parent, and is cut at a's end
    assert got == {spans.NONE: [(0, 1), (9, 10)], "a": [(1, 2), (4, 6)],
                   "b": [(2, 3)], "c": [(3, 4)], "d": [(6, 9)]}
    assert spans._flatten([], 0, 5) == {spans.NONE: [(0, 5)]}


def test_idle_of_a_recorded_trace_is_split_by_innermost_span(att):
    assert 0.1 < att["window_s"] < 1.5
    assert 0 < att["idle_s"] < att["window_s"]
    by = att["idle_by_span"]
    assert sum(by.values()) == pytest.approx(att["idle_s"], abs=1e-6)
    assert set(by) <= NAMES | {spans.COMPILE_ROW, spans.NONE}
    assert by["pool.put"] >= 0.029                    # two 15 ms sleeps
    assert by["kv.encode"] >= 0.019                   # two 10 ms sleeps
    assert by[spans.NONE] >= 0.039                    # two 20 ms sleeps
    assert by[spans.COMPILE_ROW] > 0.01               # kv.decode compiles anew
    assert att["untraced_idle_s"] == pytest.approx(by[spans.NONE], abs=1e-6)


def test_each_span_name_counts_its_spans_and_their_device_time(att):
    assert set(att["spans"]) == NAMES
    for name, v in att["spans"].items():
        assert v["count"] == 2, name
        assert v["busy_s"] + v["idle_s"] == pytest.approx(v["span_s"])
        assert 0 <= v["idle_s"] <= v["span_s"]
    page = att["spans"]["engine.page"]
    inner = sum(att["spans"][n]["span_s"] for n in ("kv.encode", "pool.put",
                                                     "kv.decode", "kv.restore"))
    assert inner <= page["span_s"]
    assert page["idle_s"] >= att["idle_by_span"]["pool.put"] + att["idle_by_span"]["kv.encode"]
    assert att["spans"]["pool.put"]["busy_s"] < 1e-3      # a host sleep alone
    assert att["spans"]["engine.prefill"]["busy_s"] > 0   # the matmul


def test_the_longest_gaps_are_labelled(att):
    gaps = att["gaps"]
    assert len(gaps) == spans.TOP
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert [g[2] for g in gaps[:2]] == [spans.COMPILE_ROW] * 2
    assert [g[2] for g in gaps[2:4]] == [spans.NONE] * 2
    for off, secs, label, shares in gaps:
        assert 0 <= off < att["window_s"]
        assert label == shares[0][0]
        assert sum(c for _, c in shares) == pytest.approx(secs, abs=1e-6)


def test_the_table_names_every_row(att, capsys):
    spans.log_table(att)
    err = capsys.readouterr().err
    for label in att["idle_by_span"]:
        assert f"  {label} " in err
    assert err.count("\n  +") == spans.TOP


def test_a_trace_without_program_spans_reads_nothing():
    assert spans.attribute(tr.read_events(HARNESS_TRACE)) is None


def _run(tmp_path, trace=None, kv0=None, kv1=None):
    cell = types.SimpleNamespace(root=tmp_path, name="phi3-qlckv-chat")
    if trace is not None:
        d = tmp_path / ".bench_trace" / cell.name / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        shutil.copy(trace, d / "host.xplane.pb")
    s0, s1 = {"steps": 1}, {"steps": 9}
    if kv0 is not None:
        s0["kv"], s1["kv"] = kv0, kv1
    return dict(cell=cell, stats0=s0, stats1=s1)


def test_trace_readers_read_the_program_spans_once(tmp_path, att, capsys):
    run = _run(tmp_path, SPANS_TRACE)
    page = att["spans"]["engine.page"]
    assert metric_reader(ROOT, "page_idle_ms_per_block")(run) == pytest.approx(
        1e3 * page["idle_s"] / 2)
    assert metric_reader(ROOT, "idle_untraced_share")(run) == pytest.approx(
        100 * att["untraced_idle_s"] / att["window_s"])
    assert capsys.readouterr().err.count("spans: device idle") == 1


@pytest.mark.parametrize("metric", ["page_idle_ms_per_block", "idle_untraced_share"])
@pytest.mark.parametrize("trace", [None, HARNESS_TRACE], ids=["no-file", "no-spans"])
def test_trace_readers_read_nothing_without_program_spans(tmp_path, metric, trace):
    assert metric_reader(ROOT, metric)(_run(tmp_path, trace)) is None


KV0 = {"overflow_sections": 0, "raw_sections": 3, "blocks_paged": 7, "page_s": 1.5,
       "dense_bytes": 7 * 1000, "wire_bytes": 7 * 600}
KV1 = dict(KV0, blocks_paged=11, page_s=3.5, dense_bytes=11 * 1000, wire_bytes=7 * 600 + 1800)


def test_counter_readers_diff_the_window(tmp_path):
    run = _run(tmp_path, kv0=KV0, kv1=KV1)
    assert metric_reader(ROOT, "page_ms_per_block")(run) == pytest.approx(500.0)
    assert metric_reader(ROOT, "kv_wire_share")(run) == pytest.approx(45.0)


@pytest.mark.parametrize("metric", ["page_ms_per_block", "kv_wire_share"])
@pytest.mark.parametrize("case", ["dense", "older-program", "no-block"])
def test_counter_readers_read_nothing_without_paging(tmp_path, metric, case):
    old = {"overflow_sections": 0, "raw_sections": 3}
    kv = {"dense": (None, None), "older-program": (old, old), "no-block": (KV0, KV0)}[case]
    assert metric_reader(ROOT, metric)(_run(tmp_path, None, *kv)) is None
