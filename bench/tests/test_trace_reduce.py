from bench import trace_reduce as tr
from bench.tests.util import ROOT

TRACE = ROOT / "bench" / "tests" / "data" / "cpu.xplane.pb"


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]


def test_reduce_a_recorded_cpu_trace():
    planes = tr.read_events(TRACE)
    r = tr.reduce(planes, **tr.CPU, spans=("decode", "page"))
    assert 0.09 < r["window_s"] < 1.0                    # three 30 ms host sleeps
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["device_ops"]]
    assert any("dot" in n for n in names)                # the decode's matmul
    assert r["span_busy_s"]["decode"] > r["span_busy_s"]["page"] > 0
    labels = [lab for lab, _ in r["idle_gaps"]]
    assert set(labels) <= {"decode", "page", "bookkeeping"}
    # the 20 ms sleeps outside any span are the longest gaps
    assert labels[:3] == ["bookkeeping"] * 3
    assert all(0.019 < s < 0.03 for _, s in r["idle_gaps"][:3])
    assert [s for _, s in r["idle_gaps"]] == sorted((s for _, s in r["idle_gaps"]),
                                                   reverse=True)


def test_a_trace_without_a_device_plane_is_an_error():
    planes = tr.read_events(TRACE)
    try:
        tr.reduce(planes, **tr.TPU)
    except ValueError as e:
        assert "TPU" in str(e)
    else:
        raise AssertionError("a CPU trace reduced as a TPU trace")
