"""Helpers of the benchmark's tests: a benchmark checkout whose
configurations are tiny."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: limit of the widest logit gap at the tiny sizes below, set between
#: the readings (CPU, seeds 1, 2, 3, 2**35 + 9, both configurations):
#: sound runs 0.0094-0.0295, the e4m3 control 0.238-0.504
TINY_LIMIT = 0.1


def tiny_config(name: str) -> dict:
    """A configuration file of the benchmark cut to the program's
    reduced smoke preset (``repro.configs.reduced``), same layer kinds."""
    from repro.launch.serve import serving_config
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg = serving_config(c["program"]["arch"], reduced=True)
    c.update(program={"arch": c["program"]["arch"], "reduced": True},
             hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
             num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
             vocab_size=cfg.vocab_size, logit_gap_limit=TINY_LIMIT)
    return c


def make_root(path: Path) -> Path:
    """A benchmark checkout at ``path`` whose configurations are tiny."""
    (path / "bench").mkdir(parents=True)
    for d in ("traffic", "cells", "metrics"):
        shutil.copytree(ROOT / "bench" / d, path / "bench" / d)
    (path / "bench" / "configs").mkdir()
    for f in (ROOT / "bench" / "configs").glob("*.json"):
        (path / "bench" / "configs" / f.name).write_text(json.dumps(tiny_config(f.stem)))
    shutil.copy(ROOT / "BENCHMARK.json", path / "BENCHMARK.json")
    return path


