"""Model families of the benchmark, one module each.

A configuration file of ``bench/configs`` names its family by the key
``"family"``, and the family lives in ``bench/families/<family>.py``.
It holds everything of the benchmark that depends on the model's
architecture; the harness, the reference and the roofline counts call
it and name no model themselves. A family module provides:

- ``program_config(c)``: the serving program's ``ModelConfig`` for the
  file ``c``, checked against every size and structural property the
  file states (a mismatch raises ``ValueError``);
- ``program_params(c, seed)``: the program's parameter tree, made on
  the device from the seed;
- ``layer_kind(c, layer)``: the kind of layer ``layer`` (an int);
  layers of one kind have weights of one shape and one forward, so
  the reference compiles one program a kind;
- ``layer_weights(c, key, layer, kind)``: one layer's weights drawn
  from ``key`` (``layer`` may be traced, ``kind`` is static) and
  ``outer_weights(c, key)``: ``embed`` ``[vocab, d]``, ``final_norm``
  and ``lm_head`` ``[d, vocab]``, the reference's own weights, the
  same values ``program_params`` lays out. The reference's control
  leaves a leaf whose name ends in ``norm`` as it is and rounds every
  other one to block-32 e4m3 along its first axis, so that axis is a
  matrix's input dimension, a multiple of 32;
- ``layer_forward(c, kind, w, x)``: one layer over full causal
  sequences, ``x`` ``[N, S, d]``, in float32 at ``HIGHEST``;
- ``matmul_params(c)``, ``token_flops(c, ctx)``, ``prefill_flops(c,
  n)`` and ``kv_bytes_per_token(c)``: the counts ``bench/roofline.py``
  serves to the metrics;
- ``tiny(c)``: ``c`` cut to a size the CPU tests run, with the
  ``logit_gap_limit`` set for that size.

A family is added as files: its module here and configuration files
that name it.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HOME = Path(__file__).resolve().parent

_BY_NAME: dict = {}     # family name -> the module last loaded under it


def module(name: str, home: Path | None = None):
    """The family module ``<home>/<name>.py``, loaded once a process.
    Without ``home``, the module last loaded under ``name`` (the cell's,
    from the checkout ``harness.load_cell`` read), else this package's."""
    if home is None and name in _BY_NAME:
        return _BY_NAME[name]
    path = Path(home or HOME).resolve() / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family module {path}")
    _BY_NAME[name] = mod = _load(path)
    return mod


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
