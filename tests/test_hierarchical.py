"""Multi-host hierarchical transport tests (PR 10).

In-process (single CPU device): the per-link-class cost model
(``LINK_CLASSES``, ``AlphaBetaModel.with_link``/``wire_time(link=)``),
the ``TRANSPORT_KINDS`` validation messages, the ``choose_transport``
pod branch (flat ring is never a candidate over a two-axis group),
pod-binding validation on ``ChannelSpec``/``Channel``, the transport
layer's ``_resolve_pod`` normalization, and the registry's per-axis
link-constant cache (validation + JSON round-trip + the
``Channel._linked_model`` fold).

Multi-device (8 fake CPU devices in a subprocess): the acceptance
invariant — on a simulated 2-pod x 4-local mesh, all four collectives
through a pod-bound Channel are BIT-IDENTICAL across {one-shot over
the combined group, hierarchical, hierarchical with hop chunking}, and
the pod-bound psum matches the uncompressed sum to codec precision.
"""
import dataclasses
import json

import pytest

from repro.comm import (AlphaBetaModel, Channel, ChannelSpec,
                        TransportConfig, HIERARCHICAL, LINK_CLASSES,
                        TRANSPORT_KINDS, choose_transport,
                        modeled_flat_ring_time,
                        modeled_hierarchical_oneshot_time,
                        modeled_hierarchical_time, modeled_ring_time,
                        resolve_transport)
from repro.comm.transport import _resolve_pod
from repro.core import distributions
from repro.core.registry import TRANSPORT_CACHE_KEY, CodecRegistry
from repro.roofline import hw
from tests.md_util import run_md


@pytest.fixture()
def registry():
    reg = CodecRegistry()
    reg.register("grads", distributions.grad_counts(1 << 16))
    return reg


class TestLinkClassModel:
    def test_link_classes_and_defaults(self):
        assert LINK_CLASSES == ("ici", "dcn")
        m = AlphaBetaModel()
        # the DCN tier must default slower on both constants — that
        # asymmetry is the hierarchical schedule's reason to exist
        assert m.link_Bps("dcn") < m.link_Bps("ici")
        assert m.link_alpha("dcn") > m.link_alpha("ici")
        assert m.link_Bps("dcn") == hw.DCN_LINK_BW
        assert m.link_alpha("dcn") == hw.DCN_LATENCY_S

    def test_wire_time_charges_the_named_link(self):
        m = AlphaBetaModel(alpha_s=0.0, wire_Bps=100.0,
                           dcn_alpha_s=0.0, dcn_wire_Bps=10.0)
        assert m.wire_time(100.0) == pytest.approx(1.0)
        assert m.wire_time(100.0, link="dcn") == pytest.approx(10.0)
        with pytest.raises(ValueError, match="link class"):
            m.wire_time(1.0, link="pcie")

    def test_with_link_substitutes_one_class_only(self):
        m = AlphaBetaModel()
        m2 = m.with_link("dcn", wire_Bps=1e9, alpha_s=5e-6)
        assert m2.link_Bps("dcn") == 1e9
        assert m2.link_alpha("dcn") == 5e-6
        assert m2.link_Bps("ici") == m.link_Bps("ici")
        m3 = m.with_link("ici", wire_Bps=7e9)
        assert m3.wire_Bps == 7e9
        assert m3.dcn_wire_Bps == m.dcn_wire_Bps
        assert m.with_link("ici") is m    # no-op stays the same object


class TestTransportKinds:
    def test_kinds_snapshot(self):
        assert TRANSPORT_KINDS == ("oneshot", "ring", "hierarchical")
        assert HIERARCHICAL == TransportConfig("hierarchical")

    def test_bad_kind_message_enumerates_kinds(self):
        with pytest.raises(ValueError) as e:
            TransportConfig(kind="mesh")
        for k in TRANSPORT_KINDS:
            assert repr(k) in str(e.value)

    def test_resolve_transport_strings_and_errors(self):
        assert resolve_transport("hierarchical").kind == "hierarchical"
        with pytest.raises(ValueError) as e:
            resolve_transport("rings")
        for k in TRANSPORT_KINDS:
            assert repr(k) in str(e.value)


class TestHierarchicalModel:
    # hardware-like wire-bound regime: wire terms dominate decode
    WIRE_BOUND = AlphaBetaModel(decode_Bps=1e15, dispatch_s=0.0)

    def test_degenerates_to_flat_ring_at_one_pod(self):
        m = AlphaBetaModel(decode_Bps=1e9)
        for h in (1, 2, 4):
            ring = modeled_ring_time(m, 1e6, 4e6, 8, h)
            assert modeled_hierarchical_time(m, 1e6, 4e6, 8, 1, h) == ring
            assert modeled_flat_ring_time(m, 1e6, 4e6, 8, 1, h) == ring

    def test_wire_bound_hierarchical_beats_flat_ring(self):
        """The headline claim: batching DCN crossings into per-hop-group
        bridges beats gating every neighbor hop at DCN speed. For L=4,
        P=2 the steady-state wire ratio approaches L(P-1)/(LP-1) = 4/7."""
        m = self.WIRE_BOUND
        for L, P in ((4, 2), (8, 2), (4, 4)):
            hier = min(modeled_hierarchical_time(m, 160e6, 256e6, L, P, h)
                       for h in (1, 2, 4, 8))
            flat = min(modeled_flat_ring_time(m, 160e6, 256e6, L, P, h)
                       for h in (1, 2, 4, 8))
            assert hier < flat
        ratio = (modeled_hierarchical_time(m, 160e6, 256e6, 4, 2, 8)
                 / modeled_flat_ring_time(m, 160e6, 256e6, 4, 2, 8))
        assert ratio == pytest.approx(4 / 7, rel=0.05)

    def test_decode_bound_charges_flat_ring_decode_work(self):
        """In a decode-bound regime the topology vanishes: both
        schedules decode d-1 foreign rows (own row hidden in fill), so
        the models must agree — a hierarchical model charging L*P
        decodes would spuriously lose the benchmark gate."""
        m = AlphaBetaModel(decode_Bps=1e8)    # CPU-like, decode-bound
        hier = modeled_hierarchical_time(m, 160e6, 256e6, 4, 2, 8)
        flat = modeled_flat_ring_time(m, 160e6, 256e6, 4, 2, 8)
        assert hier <= flat * (1 + 1e-9)

    def test_never_undercuts_dcn_bridge_floor(self):
        """L*(P-1) shard copies must cross the DCN no matter how well
        the bridges pipeline — same invariant the benchmark gates."""
        for m in (self.WIRE_BOUND, AlphaBetaModel(decode_Bps=1e8)):
            for h in (1, 2, 4, 8):
                t = modeled_hierarchical_time(m, 160e6, 256e6, 4, 2, h)
                floor = 4 * (2 - 1) * 160e6 / m.link_Bps("dcn")
                assert t >= floor

    def test_choose_transport_pod_branch_never_picks_ring(self):
        """Over a two-axis group the flat ring has no executable
        schedule — the planner may only return one-shot or
        hierarchical."""
        for decode_Bps in (1e8, 1e12, 1e15):
            for wire in (1e3, 1e6, 160e6):
                t = choose_transport(wire, wire * 1.6, 4,
                                     model=AlphaBetaModel(
                                         decode_Bps=decode_Bps),
                                     pod_size=2)
                assert t.kind in ("oneshot", "hierarchical")

    def test_choose_transport_pod_branch_picks_hierarchical_when_it_wins(
            self):
        m = AlphaBetaModel(decode_Bps=1e8)    # decode-bound: overlap wins
        t = choose_transport(160e6, 256e6, 4, model=m, pod_size=2)
        assert t.kind == "hierarchical"
        one = modeled_hierarchical_oneshot_time(m, 160e6, 256e6, 4, 2)
        hier = modeled_hierarchical_time(m, 160e6, 256e6, 4, 2,
                                         t.hop_chunks)
        assert hier < one


class TestResolvePod:
    def test_hierarchical_downgrades_to_ring_without_pod(self):
        t, ax, P = _resolve_pod(TransportConfig("hierarchical", 4),
                                None, 1)
        assert (t.kind, t.hop_chunks, ax, P) == ("ring", 4, None, 1)
        t, ax, P = _resolve_pod(TransportConfig("hierarchical"), "pod", 1)
        assert (t.kind, ax, P) == ("ring", None, 1)

    def test_ring_rejected_on_pod_bound_exchange(self):
        with pytest.raises(ValueError, match="one axis"):
            _resolve_pod(TransportConfig("ring"), "pod", 2)

    def test_oneshot_and_hierarchical_keep_the_binding(self):
        for kind in ("oneshot", "hierarchical"):
            t, ax, P = _resolve_pod(TransportConfig(kind), "pod", 2)
            assert (t.kind, ax, P) == (kind, "pod", 2)


class TestChannelPodBinding:
    def _spec(self, **kw):
        return ChannelSpec(codec="grads", transport="hierarchical",
                           axis="data", axis_size=4, **kw)

    def test_pod_bound_channel_constructs(self, registry):
        ch = Channel(self._spec(pod_axis="pod", pod_axis_size=2),
                     registry=registry)
        assert (ch.pod_axis, ch.pod_size, ch.group_size) == ("pod", 2, 8)

    def test_flat_channel_reports_pod_size_one(self, registry):
        ch = Channel(ChannelSpec(codec="grads", transport="ring",
                                 axis="data", axis_size=4),
                     registry=registry)
        assert (ch.pod_axis, ch.pod_size, ch.group_size) == (None, 1, 4)

    def test_pod_axis_must_differ_from_axis(self, registry):
        with pytest.raises(ValueError, match="differ"):
            Channel(self._spec(pod_axis="data", pod_axis_size=2),
                    registry=registry)

    def test_pod_axis_needs_static_size(self, registry):
        with pytest.raises(ValueError, match="pod_axis_size"):
            Channel(self._spec(pod_axis="pod"), registry=registry)
        with pytest.raises(ValueError, match=">= 1"):
            Channel(self._spec(pod_axis="pod", pod_axis_size=0),
                    registry=registry)

    def test_pod_axis_size_without_pod_axis_rejected(self, registry):
        with pytest.raises(ValueError, match="without pod_axis"):
            Channel(ChannelSpec(codec="grads", transport="oneshot",
                                axis="data", axis_size=4,
                                pod_axis_size=2), registry=registry)

    def test_ring_rejected_with_multi_pod_binding(self, registry):
        with pytest.raises(ValueError):
            Channel(ChannelSpec(codec="grads", transport="ring",
                                axis="data", axis_size=4,
                                pod_axis="pod", pod_axis_size=2),
                    registry=registry)

    def test_spec_json_roundtrip_and_legacy_shape(self, registry):
        from repro.comm.channel import spec_from_json, spec_to_json
        spec = self._spec(pod_axis="pod", pod_axis_size=2)
        d = spec_to_json(spec)
        assert (d["pod_axis"], d["pod_axis_size"]) == ("pod", 2)
        back = spec_from_json(d, codec="grads")
        assert (back.pod_axis, back.pod_axis_size) == ("pod", 2)
        # flat specs keep their pre-pod manifest shape byte for byte
        flat = spec_to_json(ChannelSpec(codec="grads", transport="ring",
                                        axis="data", axis_size=4))
        assert "pod_axis" not in flat and "pod_axis_size" not in flat


class TestLinkConstantCache:
    def test_cache_key_snapshot(self):
        assert TRANSPORT_CACHE_KEY == ("scheme_id", "axis",
                                       "payload_bucket", "is_reduce")

    def test_validation(self, registry):
        with pytest.raises(ValueError, match="link class"):
            registry.cache_link_constants("data", "pcie", wire_Bps=1e9)
        with pytest.raises(ValueError, match="positive"):
            registry.cache_link_constants("data", "ici", wire_Bps=0.0)

    def test_json_roundtrip(self, registry):
        registry.cache_link_constants("data", "ici", wire_Bps=9e9)
        registry.cache_link_constants("pod", "dcn", wire_Bps=1.25e9,
                                      alpha_s=2e-5)
        blob = json.dumps(registry.to_json_dict())
        back = CodecRegistry.from_json_dict(json.loads(blob))
        assert back.link_cache() == registry.link_cache()
        assert back.cached_link_constants("pod")["alpha_s"] == 2e-5
        assert back.cached_link_constants("elsewhere") is None

    def test_flat_registry_json_has_no_link_section(self, registry):
        assert "link_cache" not in registry.to_json_dict()

    def test_linked_model_folds_cached_constants(self, registry):
        registry.cache_link_constants("data", "ici", wire_Bps=9e9)
        registry.cache_link_constants("pod", "dcn", wire_Bps=1.25e9,
                                      alpha_s=2e-5)
        ch = Channel(ChannelSpec(codec="grads", transport="hierarchical",
                                 axis="data", axis_size=4,
                                 pod_axis="pod", pod_axis_size=2),
                     registry=registry)
        m = ch._linked_model()
        assert m.link_Bps("ici") == 9e9
        assert m.link_Bps("dcn") == 1.25e9
        assert m.link_alpha("dcn") == 2e-5
        # a flat channel on the same registry only folds its own axis
        flat = Channel(ChannelSpec(codec="grads", transport="auto",
                                   axis="data", axis_size=4),
                       registry=registry)
        fm = flat._linked_model()
        assert fm.link_Bps("ici") == 9e9
        assert fm.link_Bps("dcn") == AlphaBetaModel().link_Bps("dcn")


MD_HIER_EQUIV = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.core import TABLE1, build_tables, distributions
from repro.comm import (Channel, ChannelSpec, CommConfig, TransportConfig,
                        plan_for_tables)

devs = jax.devices()
assert len(devs) == 8, devs
mesh = Mesh(np.array(devs).reshape(2, 4), ("pod", "d"))
counts = distributions.ffn1_counts(1 << 16)
tables = build_tables(counts, TABLE1)
plan = plan_for_tables(tables, counts, chunk_symbols=256)
cfg = CommConfig.from_plan(plan)

transports = {
    "oneshot": TransportConfig("oneshot"),
    "hier": TransportConfig("hierarchical"),
    "hier2": TransportConfig("hierarchical", 2),
}
rng = np.random.default_rng(0)
X = rng.standard_normal((8, 4096)).astype(np.float32)
X3 = rng.standard_normal((8, 8, 512)).astype(np.float32)

def run(f, x, three=False):
    inspec = P(("pod", "d"), None, None) if three else P(("pod", "d"), None)
    def g(v):
        out, ok = f(v[0])
        return out[None], ok[None]
    return jax.jit(shard_map(g, mesh=mesh, in_specs=inspec,
                             out_specs=(inspec, P(("pod", "d"))),
                             check_rep=False))(x)

outs = {}
for tname, t in transports.items():
    ch = Channel(ChannelSpec(codec=tables, cfg=cfg, transport=t,
                             axis="d", axis_size=4,
                             pod_axis="pod", pod_axis_size=2))
    cases = [
        ("all_gather", ch.all_gather, X, False),
        ("reduce_scatter",
         lambda v: (lambda r: (r.segment, r.ok))(ch.reduce_scatter(v)),
         X, False),
        ("psum", ch.psum, X, False),
        ("all_to_all", ch.all_to_all, X3, True),
    ]
    for name, chf, x, three in cases:
        o, ok = run(chf, x, three)
        assert np.asarray(ok).all(), (tname, name)
        outs[(tname, name)] = np.asarray(o)
        print(tname, name, "ok")

for name in ("all_gather", "reduce_scatter", "psum", "all_to_all"):
    for tname in ("hier", "hier2"):
        np.testing.assert_array_equal(outs[("oneshot", name)],
                                      outs[(tname, name)])
    print(name, "bit-identical across transports")

# sanity vs uncompressed semantics: psum close to the true sum
true = X.sum(axis=0, keepdims=True).repeat(8, 0)
err = np.abs(outs[("oneshot", "psum")] - true).max() / np.abs(true).max()
assert err < 0.1, err
print("HIER EQUIV OK")
"""


MD_HIER_TRAIN = """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.comm import calibrate_for_gradients
from repro.comm.calibrate import histogram_of_tree
from repro.configs import get_config, reduced
from repro.core import CodecRegistry
from repro.data import DataConfig, SyntheticDataset
from repro.launch.mesh import make_device_mesh
from repro.models import init_params
from repro.parallel import sharding as shd
from repro.training import (OptConfig, TrainConfig,
                            init_compressed_opt_state,
                            make_compressed_step)

cfg = reduced(get_config("gemma-2b-sft"))
mesh = make_device_mesh(pods=2)
assert mesh.axis_names == ("pod", "data", "model"), mesh.axis_names
opt_cfg = OptConfig(lr=3e-4, total_steps=4, warmup_steps=1)
train_cfg = TrainConfig(batch_axes=("pod", "data"))
data = SyntheticDataset(DataConfig(
    vocab_size=cfg.vocab_size, seq_len=128 - cfg.frontend_prefix_len,
    global_batch=8))

with shd.use_mesh(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0))
    b0 = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    tables, plan = calibrate_for_gradients(cfg, params, b0)
    # this reduced model's flat gradient holds only tens of chunks per
    # rank, so the planner's ~1-slot escape pool can overflow on heavy-
    # tailed steps (see tests/test_train_integration.py) — make the
    # wire unconditionally lossless so ok reflects routing, not sizing
    plan = dataclasses.replace(plan, pool_slots_per_1k=1024)
    registry = CodecRegistry()
    registry.register_tables("grads", tables, plan)
    registry.register("params", histogram_of_tree(params),
                      chunk_symbols=plan.chunk_symbols,
                      pool_slots_per_1k=1024)
    step = jax.jit(make_compressed_step(
        cfg, opt_cfg, train_cfg, mesh, registry,
        transport="hierarchical", hierarchical_wire=True))
    opt_state = init_compressed_opt_state(
        cfg, mesh, train_cfg, registry, opt_cfg)
    losses = []
    for i in range(3):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt_state, metrics = step(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        assert bool(metrics["ok"]), metrics
print("losses", losses)
assert losses[-1] < losses[0], losses
print("HIER TRAIN OK")
"""


class TestHierarchicalCollectives:
    def test_bit_identical_to_oneshot_all_collectives(self):
        """Acceptance: on a 2-pod x 4-local mesh all four collectives
        through a pod-bound Channel match the combined-group one-shot
        bit for bit, with and without hop chunking."""
        out = run_md(MD_HIER_EQUIV, timeout=1800)
        assert "HIER EQUIV OK" in out

    def test_training_step_over_pod_mesh(self):
        """The --pods wire end to end: a compressed train step on a
        (2, 2, 2) pod x data x model mesh with hierarchical_wire=True
        runs, keeps comm_ok, and the loss decreases."""
        out = run_md(MD_HIER_TRAIN, timeout=1800)
        assert "HIER TRAIN OK" in out
