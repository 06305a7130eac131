import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.util import ROOT, make_root


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(ROOT, m["name"]))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_phi3s_two_configurations_state_one_model():
    """The dense twin's configuration differs only in name and in how
    its cells serve it, so the twins price paging and nothing else."""
    def model(name):
        c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
        return {k: v for k, v in c.items() if k not in ("name", "serving")}
    assert model("phi3-mini-3.8b-densekv") == model("phi3-mini-3.8b")


def test_a_cell_added_as_data_only_is_found_by_name(tmp_path):
    root = make_root(tmp_path / "b")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "bench" / "traffic" / "chat-closed8.json").read_text())
    mix.update(clients=2, prime=[[64, 40], [128, 60]])
    (root / "bench" / "traffic" / "chat-closed2.json").write_text(json.dumps(mix))
    (root / "bench" / "cells" / "glm3-dense-chat2.json").write_text(json.dumps(
        {"kv_cache": "none", "kv_paging": "sync", "kv_block": 128, "slots": 2,
         "max_seq_len": 1024, "pool_bytes": 0}))
    bench["workloads"].append({"name": "glm3-dense-chat2", "config": "chatglm3-6b",
                               "traffic": "chat-closed2", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(root, "glm3-dense-chat2")
    assert cell.mix["clients"] == 2 and cell.settings["slots"] == 2
    assert cell.config["name"] == "chatglm3-6b"
    assert "kv_codec_roofline" not in {m["name"] for m in cell.per_layer}
    out = harness.run_cell(cell, 2**33 + 1, 2.0, False, 0.0)
    assert out["correct"] and out["device"]["platform"] == "cpu"
    with pytest.raises(KeyError):
        harness.load_cell(root, "no-such-cell")


def test_the_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
                        "phi3-dense-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and p.stdout.strip() == ""


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("run.py", "harness.py", "__init__.py"):
        (tmp_path / "bench" / f).write_text((ROOT / "bench" / f).read_text())
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
                        "phi3-dense-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_config_the_program_does_not_serve_is_refused():
    from bench.tests.util import tiny_config
    c = tiny_config("phi3-mini-3.8b")
    c["intermediate_size"] += 1
    with pytest.raises(ValueError, match="intermediate_size"):
        harness.program_config(c)
