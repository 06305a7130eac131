"""A configuration brings its model family as files: found by name,
refused without one, and the dense family's reference reads as it did
before it moved out of the harness."""
import json
import shutil

import numpy as np
import pytest

from bench import families, harness, roofline
from bench.reference import Reference
from bench.tests.util import make_root, tiny_config

#: ``Reference.gaps`` on the tiny configurations at seed 2**31 + 7, read
#: at commit 10252ae (before the dense family moved out of
#: bench/reference.py and bench/weights.py): widest gap of the served
#: tokens, of the e4m3 control's first choices, tokens read
PINNED = {"phi3-mini-3.8b": (4.344709873199463, 0.40407872200012207, 33),
          "chatglm3-6b": (5.273025035858154, 0.21697521209716797, 33)}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_reference_reads_as_before_the_move(name):
    c = tiny_config(name)
    rng = np.random.Generator(np.random.PCG64(3))
    seqs = [(rng.integers(0, c["vocab_size"], p), rng.integers(0, c["vocab_size"], n))
            for p, n in ((5, 9), (12, 4), (3, 20))]
    ref = Reference(families.module(c["family"]), c, 2**31 + 7, 3, 32)
    got = ref.gaps(seqs, control=True)
    gap, control, tokens = PINNED[name]
    assert got["tokens"] == tokens
    # float32 at HIGHEST: the same program reads the same to rounding
    assert got["logit_gap_max"] == pytest.approx(gap, rel=1e-6)
    assert got["control_gap_max"] == pytest.approx(control, rel=1e-6)


def _with_config(root, name, c):
    """Add configuration ``name`` and a cell serving it as glm3-qlckv-chat does."""
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(c))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": c["source"],
                             "file": f"bench/configs/{name}.json",
                             "reduced": c["reduced"], "why": "test"})
    bench["workloads"].append({"name": f"{name}-chat", "config": name,
                               "traffic": "chat-closed8", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / "bench" / "cells" / "glm3-qlckv-chat.json",
                root / "bench" / "cells" / f"{name}-chat.json")
    return f"{name}-chat"


def test_a_family_added_as_files_only_serves_its_cell(tmp_path):
    root = make_root(tmp_path / "b")
    fam = root / "bench" / "families"
    shutil.copy(fam / "dense.py", fam / "decoder.py")
    c = json.loads((root / "bench" / "configs" / "chatglm3-6b.json").read_text())
    cell = harness.load_cell(root, _with_config(root, "glm3-decoder", dict(
        c, name="glm3-decoder", family="decoder")))
    assert cell.family.__file__ == str((fam / "decoder.py").resolve())
    # the metrics' counts come from the cell's family too
    assert roofline.token_flops(cell.config, 7) == cell.family.token_flops(cell.config, 7)
    out = harness.run_cell(cell, 2**33 + 3, 2.0, False, 0.0)
    assert out["correct"] and out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_a_configuration_without_its_family_is_refused(tmp_path, family):
    root = make_root(tmp_path / "b")
    c = json.loads((root / "bench" / "configs" / "phi3-mini-3.8b.json").read_text())
    c.pop("family")
    if family:
        c["family"] = family
    cell = _with_config(root, "phi3-orphan", dict(c, name="phi3-orphan"))
    looked_for = ("configs/phi3-orphan.json" if family is None
                  else f"families/{family}.py")
    with pytest.raises((ValueError, FileNotFoundError)) as err:
        harness.load_cell(root, cell)
    assert f"/bench/{looked_for}" in str(err.value)
