"""Profiler spans and paging counters of the serving engine.

The engine marks where the host spends a step with
``jax.profiler.TraceAnnotation`` spans and counts its host-path paging
in ``stats()["kv"]``. Recorded here in a real profiler trace of a tiny
paged engine (QLC, sync paging) and its dense twin: the span names,
their nesting and metadata, the counters against the spans and the
pooled blocks, and the tokens with the profiler on and off.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.serving import BlockPool, Engine, GenerationRequest
from repro.serving.kv_cache import KVCacheSpec

PROMPTS = (6, 9, 5)
NEW_TOKENS = 6
PAGE_CHILDREN = ("kv.encode", "pool.put", "kv.decode", "kv.restore")


@pytest.fixture(scope="module")
def model():
    from repro.models import init_params
    cfg = reduced(get_config("phi3-mini-3.8b"), frontend=None,
                  frontend_prefix_len=0, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(model, paged: bool, on_put=None):
    """Serve three requests through two slots; returns the engine and
    each request's tokens."""
    cfg, params = model
    eng = Engine(params, cfg, max_seq_len=32, max_batch=2,
                 kv_spec=KVCacheSpec(block_tokens=4) if paged else None,
                 pool=BlockPool(1 << 30) if paged else None)
    if on_put is not None:
        put = eng.pool.put
        eng.pool.put = lambda block: (on_put(block), put(block))[1]
    rng = np.random.default_rng(3)
    handles = [eng.submit(GenerationRequest(
        prompt=rng.integers(0, cfg.vocab_size, n), max_new_tokens=NEW_TOKENS,
        request_id=f"q{i}")) for i, n in enumerate(PROMPTS)]
    eng.run()
    return eng, {h: eng.poll(h).tokens.tolist() for h in handles}


def _spans(trace_dir):
    """The engine's spans of a trace: ``[name, start, end, line, stats,
    parent name]`` in start order (parent: the innermost engine span
    enclosing it on the same host line, or None)."""
    from jax.profiler import ProfileData
    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.split(".")[0] in ("engine", "kv", "pool"):
                    out.append([e.name, e.start_ns, e.start_ns + e.duration_ns,
                                (plane.name, li), dict(e.stats), None])
    for sp in out:
        enclosing = [o for o in out if o is not sp and o[3] == sp[3]
                     and o[1] <= sp[1] and sp[2] <= o[2]]
        if enclosing:
            sp[5] = min(enclosing, key=lambda o: o[2] - o[1])[0]
    return sorted(out, key=lambda sp: sp[1])


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """Both engines served under one profiler trace, paged first."""
    d = tmp_path_factory.mktemp("trace")
    blocks = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        paged, paged_toks = _serve(model, True, on_put=blocks.append)
        dense, dense_toks = _serve(model, False)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(d)
    steps = [sp for sp in spans if sp[0] == "engine.step"]
    first_dense = steps[paged.stats()["steps"]][1]
    return dict(paged=paged, dense=dense, blocks=blocks,
                paged_spans=[sp for sp in spans if sp[1] < first_dense],
                dense_spans=[sp for sp in spans if sp[1] >= first_dense],
                toks={"paged": paged_toks, "dense": dense_toks})


def test_every_span_is_recorded(traced):
    names = {sp[0] for sp in traced["paged_spans"]}
    assert names == {"engine.step", "engine.admit", "engine.prefill",
                     "kv.calibrate", "engine.slot_write", "engine.decode",
                     "engine.page", *PAGE_CHILDREN}
    assert {sp[0] for sp in traced["dense_spans"]} == {
        "engine.step", "engine.admit", "engine.prefill", "engine.slot_write",
        "engine.decode"}


def test_spans_nest_where_the_work_happens(traced):
    spans = traced["paged_spans"]
    parent = {"engine.step": None, "engine.admit": "engine.step",
              "engine.prefill": "engine.admit", "kv.calibrate": "engine.admit",
              "engine.decode": "engine.step", "pool.put": "engine.page",
              "kv.encode": "engine.page", "kv.decode": "engine.page",
              "kv.restore": "engine.page"}
    for name, _, _, _, _, up in spans:
        if name in parent:
            assert up == parent[name], (name, up)
        elif name == "engine.page":
            assert up in ("engine.step", "engine.admit")
        else:
            assert up in ("engine.admit", "engine.page"), (name, up)
    assert traced["paged"].stats()["steps"] == sum(
        sp[0] == "engine.step" for sp in spans)
    assert [sp[4]["step"] for sp in spans if sp[0] == "engine.step"] == list(
        range(1, traced["paged"].stats()["steps"] + 1))


def test_each_paged_block_codes_every_layer_inside_its_page_span(traced):
    spans = traced["paged_spans"]
    # one state slot per entry of the layer pattern, groups stacked
    layers = {f"l{i}" for i in range(len(traced["paged"].cfg.layer_kinds()))}
    pages = [sp for sp in spans if sp[0] == "engine.page"]
    assert pages
    for page in pages:
        inside = [sp for sp in spans if sp[3] == page[3]
                  and page[1] <= sp[1] and sp[2] <= page[2] and sp is not page]
        for child in PAGE_CHILDREN:
            assert sorted(sp[4]["layer"] for sp in inside if sp[0] == child) \
                == sorted(layers), child
        assert [sp[0] for sp in inside if sp[0] == "engine.slot_write"] == [
            "engine.slot_write"]


@pytest.mark.parametrize("name", ["engine.admit", "engine.page",
                                  "engine.slot_write"])
def test_request_spans_carry_their_request_id(traced, name):
    rids = [sp[4]["rid"] for sp in traced["paged_spans"] if sp[0] == name]
    assert set(rids) == {f"q{i}" for i in range(len(PROMPTS))}
    if name == "engine.admit":
        assert sorted(sp[4]["prompt_len"] for sp in traced["paged_spans"]
                      if sp[0] == name) == sorted(PROMPTS)


def test_blocks_paged_counts_the_page_spans(traced):
    kv = traced["paged"].stats()["kv"]
    pages = [sp for sp in traced["paged_spans"] if sp[0] == "engine.page"]
    assert kv["blocks_paged"] == len(pages)
    # every request pages each completed 4-token block it absorbed
    assert kv["blocks_paged"] == sum(
        (n + NEW_TOKENS - 1) // 4 for n in PROMPTS)
    span_s = sum(sp[2] - sp[1] for sp in pages) * 1e-9
    assert span_s == pytest.approx(kv["page_s"], rel=0.1)


def test_byte_counters_sum_the_pooled_blocks(traced):
    kv, blocks = traced["paged"].stats()["kv"], traced["blocks"]
    assert len(blocks) == kv["blocks_paged"] * len(traced["paged"].cfg.layer_kinds())
    assert kv["dense_bytes"] == sum(b.dense_bytes for b in blocks) > 0
    assert kv["wire_bytes"] == sum(b.wire_bytes for b in blocks) > 0


def test_the_dense_engine_counts_no_paging(traced):
    assert "kv" not in traced["dense"].stats()
    assert not any(sp[0].startswith(("kv.", "pool.")) or sp[0] == "engine.page"
                   for sp in traced["dense_spans"])


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_tokens_are_the_same_with_the_profiler_off(model, traced, kind):
    _, toks = _serve(model, kind == "paged")
    assert toks == traced["toks"][kind]
    assert all(len(t) == NEW_TOKENS for t in toks.values())


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_prefill_spans_carry_their_mode(traced, kind):
    spans = traced[f"{kind}_spans"]
    assert [sp[4]["mode"] for sp in spans if sp[0] == "engine.prefill"] == [
        "bulk"] * len(PROMPTS)
    st = traced[kind].stats()
    assert st["prefill_bulk_tokens"] == st["prefill_tokens"] == sum(PROMPTS)
