"""Helpers of the benchmark's tests: a benchmark checkout whose
configurations are tiny."""
import json
import shutil
from pathlib import Path

from bench import families

ROOT = Path(__file__).resolve().parents[2]

#: limit of the widest logit gap at the tiny sizes of the dense family
TINY_LIMIT = families.module("dense").TINY_LIMIT


def tiny_config(name: str) -> dict:
    """A configuration file of the benchmark cut by its family's
    ``tiny`` to a size the CPU runs."""
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return families.module(c["family"]).tiny(c)


def make_root(path: Path) -> Path:
    """A benchmark checkout at ``path`` whose configurations are tiny."""
    (path / "bench").mkdir(parents=True)
    for d in ("traffic", "cells", "metrics", "families"):
        shutil.copytree(ROOT / "bench" / d, path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (path / "bench" / "configs").mkdir()
    for f in (ROOT / "bench" / "configs").glob("*.json"):
        (path / "bench" / "configs" / f.name).write_text(json.dumps(tiny_config(f.stem)))
    shutil.copy(ROOT / "BENCHMARK.json", path / "BENCHMARK.json")
    return path
