"""One run of one benchmark cell: set-up, measured window, check.

Everything a cell needs is found by name: its entry in
``BENCHMARK.json``, ``bench/cells/<cell>.json`` (engine settings),
``bench/configs/<config>.json`` (sizes, source, the limit of the
correctness check) and ``bench/traffic/<mix>.json``; each per-layer
metric is read by ``bench/metrics/<metric>.py``. A cell is added by
adding files and entries, never by editing this module.

The run (``run_cell``):

1. makes the weights on the device from the seed (``bench/weights.py``)
   and builds ``repro.serving.Engine`` with the cell's settings;
2. set-up: admits one request per client (one of each prompt bucket,
   so every prefill length compiles; in a paged cell their prompt
   blocks page out, which calibrates the KV codec and compiles its
   path), then steps until every client has a token;
3. measures for ``seconds``: a closed loop in which each client submits
   its next request as soon as its last one finished, one engine step
   at a time, every token stamped with the host clock as the step
   returns;
4. reads the peak device memory, frees the program's state, and runs
   the reference over a seeded sample of the finished requests
   (``bench/reference.py``): ``correct`` holds when no served token's
   reference logit lies further below the reference's best than the
   configuration's limit.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import logging
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import roofline, traffic, window
from bench.window import Record

SPANS = ("admit", "prefill", "decode", "page", "step")
SAMPLE_ROWS = 6          # requests the reference reads per run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Cells, found by name
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    mix: dict
    settings: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root, name: str) -> Cell:
    """The cell ``name`` of the benchmark at ``root`` and its files."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    home = root / "bench"
    config = json.loads((home / "configs" / f"{entry['config']}.json").read_text())
    mix = traffic.load(home / "traffic" / f"{entry['traffic']}.json")
    settings = json.loads((home / "cells" / f"{name}.json").read_text())
    return Cell(name, entry, config, mix, settings,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


def metric_reader(root, name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# The program under test
# --------------------------------------------------------------------------

# configuration-file key -> the program's ModelConfig attribute
_SIZES = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "resolved_head_dim",
          "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta", "partial_rotary_factor": "rope_fraction"}


def program_config(c: dict):
    """The program's configuration for a benchmark configuration file,
    checked against every size the file states."""
    from repro.launch.serve import serving_config
    prog = c["program"]
    cfg = serving_config(prog["arch"], reduced=prog.get("reduced", False))
    bad = [f"{k}: file {c[k]} != program {getattr(cfg, a)}"
           for k, a in _SIZES.items() if c[k] != getattr(cfg, a)]
    if (cfg.family, cfg.activation, cfg.param_dtype, cfg.tie_embeddings,
            cfg.sliding_window, cfg.layer_kinds()) != (
            "dense", "swiglu", c["torch_dtype"], False, None, ("attention",)):
        bad.append("not a dense bf16 SwiGLU decoder with an untied head")
    if bad:
        raise ValueError(f"{c['name']}: the program serves another model: {bad}")
    return cfg


def build_params(c: dict, cfg, seed: int):
    """The benchmark's weights in the program's layout, checked leaf by
    leaf against the program's own parameter tree."""
    import jax

    from bench import weights
    from repro.models import init_params
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    params = weights.program_params(c, seed)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's tree: {got} != {exp}")
    return params


def build_engine(cell: Cell, params, cfg, mesh):
    from repro.launch.serve import kv_cache_spec
    from repro.serving import BlockPool, Engine
    s = cell.settings
    spec = kv_cache_spec(s["kv_cache"], s["kv_block"], s["kv_paging"])
    return Engine(params, cfg, max_seq_len=s["max_seq_len"], max_batch=s["slots"],
                  kv_spec=spec,
                  pool=BlockPool(int(s["pool_bytes"])) if spec is not None else None,
                  kv_paging=s["kv_paging"], mesh=mesh)


# --------------------------------------------------------------------------
# Compiles inside the window
# --------------------------------------------------------------------------

class CompileWatch:
    """Counts executables built (compiled, or loaded from the persistent
    cache) while ``open``, with the names JAX logs for them."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.open, self.count, self.names = False, 0, []
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        watch = self

        class _Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if watch.open and msg.startswith("Compiling "):
                    watch.names.append(msg[len("Compiling "):][:160])

        self._handler = _Names(level=logging.DEBUG)
        self._logger = logging.getLogger("jax")
        self._logger.addHandler(self._handler)
        self._logger.setLevel(logging.DEBUG)
        self._logger.propagate = False
        for h in list(self._logger.handlers):
            if h is not self._handler:
                h.setLevel(logging.ERROR)

    def _on_event(self, event, duration, **_):
        if event == self._event and self.open:
            self.count += 1


#: one watch a process: JAX keeps its listeners for good
compile_watch = functools.cache(CompileWatch)


# --------------------------------------------------------------------------
# The closed loop
# --------------------------------------------------------------------------

class Clients:
    """A closed loop of ``n`` clients over one request stream: a client
    submits its next request the moment its last one finished."""

    def __init__(self, eng, stream, n: int, clock=time.perf_counter):
        from repro.serving import GenerationRequest
        self._req = GenerationRequest
        self.eng, self.stream, self.clock = eng, stream, clock
        self.active: List[Optional[Record]] = [None] * n
        self.records: List[Record] = []

    def submit_idle(self):
        for c, rec in enumerate(self.active):
            if rec is None:
                req = next(self.stream)
                rec = Record(req.index, req.prompt, req.max_new_tokens, self.clock())
                rec.handle = self.eng.submit(self._req(
                    prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                    request_id=f"r{req.index}"))
                self.active[c] = rec
                self.records.append(rec)

    def step(self) -> float:
        """One engine step; stamps every token it returned. Returns the
        time the step returned."""
        self.eng.step()
        t = self.clock()
        for c, rec in enumerate(self.active):
            st = self.eng.poll(rec.handle)
            new = len(st.tokens) - len(rec.times)
            if new:
                rec.times += [t] * new
                rec.tokens = st.tokens
            rec.state = st.state
            if st.state in ("finished", "rejected"):
                self.active[c] = None
        return t


def _prefill_s(stats: dict) -> float:
    return stats["ms_per_token_prefill"] * stats["prefill_tokens"] / 1e3


def _decode_s(stats: dict) -> float:
    return stats["ms_per_token_decode"] * stats["decode_tokens"] / 1e3


def _annotate(eng, counts: Dict[str, int]):
    """Wrap the engine's own calls in profiler spans (traced runs only)
    so the trace can say what the host was doing in each idle gap."""
    import jax

    def wrap(fn, name):
        def inner(*a, **k):
            counts[name] = counts.get(name, 0) + 1
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner

    eng._start = wrap(eng._start, "admit")
    eng._prefill = wrap(eng._prefill, "prefill")
    eng._step_fn = wrap(eng._step_fn, "decode")
    eng._evict_slot = wrap(eng._evict_slot, "page")
    step = eng.step
    eng.step = wrap(step, "step")


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

def sample_finished(records, seed: int, rows: int = SAMPLE_ROWS):
    """A seeded sample of the finished requests, the longest included."""
    done = [r for r in records if r.state == "finished"]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.Generator(np.random.PCG64(int(seed) + 1))
    pick = rng.permutation(len(rest))[:rows - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
             device=None, trace_dir: Optional[Path] = None, control: bool = False) -> dict:
    """Set up, measure, check; returns the result line's object. With
    ``control`` the reference also reads its lower-precision control
    on the same sample (``compared["control_gap_max"]``, the reading a
    limit is set below; ``bench/limits.py``)."""
    import jax

    from repro.launch.mesh import make_device_mesh
    from repro.parallel import sharding as shd

    dev = device or jax.devices()[0]
    watch = compile_watch()
    watch.count, watch.names = 0, []
    c, s = cell.config, cell.settings
    cfg = program_config(c)
    mesh = make_device_mesh()
    counts: Dict[str, int] = {}
    with shd.use_mesh(mesh):
        params = build_params(c, cfg, seed)
        eng = build_engine(cell, params, cfg, mesh)
        stream = traffic.stream(cell.mix, seed, c["vocab_size"])
        loop = Clients(eng, stream, cell.mix["clients"])
        loop.submit_idle()
        while any(r is not None and not r.times for r in loop.active):
            loop.step()
        setup_s = time.perf_counter() - t_process
        log(f"setup: {setup_s:.3f} s (process start to window), "
            f"{len(loop.records)} requests primed")

        if trace:
            _annotate(eng, counts)
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        steps = []
        stats0 = eng.stats()
        watch.open = True
        start = time.perf_counter()
        end = start + seconds
        t = start
        ctx = jax.profiler.TraceAnnotation("window") if trace else None
        if ctx:
            ctx.__enter__()
        # The window is (start, end]: the step running at its close
        # finishes, and what it returns falls outside.
        while t < end:
            loop.submit_idle()
            before = eng.stats() if trace else None
            t0 = time.perf_counter()
            t = loop.step()
            if trace and t <= end:
                after = eng.stats()
                steps.append({"wall_s": t - t0,
                              "prefill_s": _prefill_s(after) - _prefill_s(before),
                              "decode_s": _decode_s(after) - _decode_s(before)})
        if ctx:
            ctx.__exit__(None, None, None)
        watch.open = False
        stats1 = eng.stats()
        if trace:
            jax.profiler.stop_trace()
    records = loop.records
    e2e = window.end_to_end(records, start, end)
    in_window = [r for r in records if start <= r.submit <= end]
    attempted = len(in_window)
    failed = sum(r.state == "rejected" for r in in_window)
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    log(f"window: {end - start:.3f} s (last step returned {t - end:.3f} s after it), "
        f"{len(in_window)} requests submitted, {e2e['n_ttft']} first tokens, "
        f"{e2e['n_gaps']} token gaps, {stats1['steps'] - stats0['steps']} engine steps, "
        f"{window.output_tokens(records, start, end)} output tokens")
    log(f"compiles in window: {watch.count} {sorted(set(watch.names))}")
    log(f"peak_bytes_in_use after window: {peak}")

    run = dict(cell=cell, config=c, settings=s, records=records, start=start,
               end=end, stats0=stats0, stats1=stats1, steps=steps, counts=counts,
               peaks=roofline.peaks(dev.device_kind) if dev.platform == "tpu" else None,
               trace=None)
    reduced = None
    if trace:
        from bench import trace_reduce
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        planes = trace_reduce.read_events(files[-1])
        where = trace_reduce.TPU if dev.platform == "tpu" else trace_reduce.CPU
        reduced = trace_reduce.reduce(planes, **where, spans=SPANS)
        run["trace"] = reduced

    # ---- free the program, then the reference ---------------------------
    sample = sample_finished(records, seed)
    del eng, params, loop
    gc.collect()
    checks = {}
    if sample:
        from bench.reference import Reference
        t0 = time.perf_counter()
        ref = Reference(c, seed, SAMPLE_ROWS, s["max_seq_len"])
        got = ref.gaps([(r.prompt, r.tokens) for r in sample], control=control)
        log(f"reference: {len(sample)} requests, {got['tokens']} served tokens, "
            f"{time.perf_counter() - t0:.3f} s")
        checks["logit_gap_max"] = [got["logit_gap_max"], c["logit_gap_limit"]]
        if control:
            checks["control_gap_max"] = [got["control_gap_max"], c["logit_gap_limit"]]
    else:
        checks["finished_requests"] = [0, 1]
    correct = bool(sample) and checks["logit_gap_max"][0] <= checks["logit_gap_max"][1]

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = metric_reader(cell.root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["compared"] = checks
    for name, (value, limit) in checks.items():
        log(f"check {name}: {value} limit {limit}")
    return out
