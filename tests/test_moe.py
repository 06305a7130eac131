"""MoE dispatch parity: gspmd / grouped_local / shardmap_a2a.

The contract under test (ISSUE 8 acceptance):

* routing (expert indices, gates, capacity drops) is bit-identical
  across impls — shardmap_a2a reconstructs gspmd's global cumsum
  positions from an integer counts gather, so this holds exactly even
  on the compressed wire;
* uncompressed shardmap_a2a output is bit-identical to gspmd;
* the compressed wire is bit-identical to its ``enabled=False``
  raw-e4m3 twin (the repo's lossless contract) and within e4m3
  tolerance of gspmd;
* the ring-pipelined a2a transport is bit-identical to one-shot.

Multi-device checks run in a fake-device subprocess (``md_util``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.planner import (AlphaBetaModel, choose_a2a_transport,
                                modeled_a2a_ring_time,
                                modeled_oneshot_time)
from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import moe

from md_util import run_md


def tiny_cfg(**moe_over) -> ModelConfig:
    m = MoEConfig(num_experts=4, top_k=2, d_expert=8,
                  num_shared_experts=1)
    if moe_over:
        m = dataclasses.replace(m, **moe_over)
    return ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                       num_heads=2, num_kv_heads=2, d_ff=32,
                       vocab_size=64, moe=m)


def with_impl(cfg: ModelConfig, impl: str, **moe_over) -> ModelConfig:
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl=impl, **moe_over))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
    return cfg, params, x


class TestRouting:
    def test_unknown_impl_is_typed_error(self, setup):
        cfg, params, x = setup
        with pytest.raises(ValueError, match="supported impls"):
            moe.moe_block(params, x, with_impl(cfg, "bogus"))

    def test_route_returns_probs_matching_logits(self, setup):
        cfg, params, x = setup
        x_flat = x.reshape(-1, cfg.d_model)
        idx, gates, probs = moe._route(params, x_flat, cfg.moe)
        ref = jax.nn.softmax(moe._router_logits(params, x_flat), axis=-1)
        np.testing.assert_array_equal(np.asarray(probs), np.asarray(ref))
        assert idx.shape == (32, 2) and gates.shape == (32, 2)

    def test_aux_loss_from_routing_artifacts(self, setup):
        cfg, params, x = setup
        x_flat = x.reshape(-1, cfg.d_model)
        idx, _gates, probs = moe._route(params, x_flat, cfg.moe)
        aux = moe.aux_load_balance_loss(probs, idx, cfg.moe)
        # reference: Switch-style balance from a fresh einsum
        logits = jnp.einsum("nd,de->ne", x_flat, params["router"])
        ref_probs = jax.nn.softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(idx, 4, dtype=jnp.float32).sum(1)
        ref = 4 * jnp.sum(onehot.mean(0) * ref_probs.mean(0))
        np.testing.assert_allclose(float(aux), float(ref), rtol=1e-6)
        # perfectly uniform routing -> loss ~= top_k
        uni = jnp.full((32, 4), 0.25)
        uidx = jnp.tile(jnp.arange(2), (32, 1))
        np.testing.assert_allclose(
            float(moe.aux_load_balance_loss(uni, uidx, cfg.moe)),
            cfg.moe.top_k, rtol=1e-6)

    def test_gspmd_vs_grouped_local_single_group(self, setup):
        cfg, params, x = setup
        y_g = jax.jit(lambda: moe.moe_block(params, x, cfg))()
        y_1 = jax.jit(lambda: moe.moe_block(
            params, x, with_impl(cfg, "grouped_local",
                                 dispatch_groups=1)))()
        np.testing.assert_array_equal(np.asarray(y_g), np.asarray(y_1))

    def test_dispatch_traffic_shapes(self, setup):
        cfg, params, x = setup
        buf, out_e = moe.dispatch_traffic(params, x, cfg)
        c = moe._capacity(32, cfg.moe)
        assert buf.shape == (4, c, 16) and out_e.shape == (4, c, 16)


class TestShardmapGeometry:
    def test_needs_mesh(self, setup):
        cfg, params, x = setup
        with pytest.raises(ValueError, match="mesh with a 'model' axis"):
            moe.moe_block(params, x, with_impl(cfg, "shardmap_a2a"))

    def test_divisibility_errors(self):
        class M:
            axis_names = ("data", "model")
            shape = {"data": 2, "model": 4}
        with pytest.raises(ValueError, match="divisible"):
            moe.shardmap_a2a_geometry(tiny_cfg(), 33, M())

        class M8:
            axis_names = ("model",)
            shape = {"model": 8}
        with pytest.raises(ValueError, match="num_experts"):
            moe.shardmap_a2a_geometry(tiny_cfg(), 32, M8())

    def test_geometry_row_values(self):
        from jax.sharding import Mesh
        # geometry is mesh-shape math only; fake a 2x4 mesh via a
        # 1-device mesh is impossible, so compute on an abstract stand-in
        class M:
            axis_names = ("data", "model")
            shape = {"data": 2, "model": 4}
        g = moe.shardmap_a2a_geometry(tiny_cfg(), 32, M())
        # ng = 32/(2*4) = 4; C = 32*2*1.25//4 = 20; c_send = min(4,20)=4
        assert g == {"ng": 4, "capacity": 20, "c_send": 4,
                     "row_values": 1 * 4 * 16, "axis_size": 4}


class TestA2ATransportModel:
    def test_degenerate_axis(self):
        m = AlphaBetaModel()
        assert modeled_a2a_ring_time(m, 100, 400, 1) == \
            m.decode_time(400)
        assert choose_a2a_transport(100, 400, 1).kind == "oneshot"

    def test_decode_bound_prefers_ring(self):
        slow = AlphaBetaModel(decode_Bps=1e9)
        t = choose_a2a_transport(1 << 20, 4 << 20, 8, model=slow)
        assert t.kind == "ring"
        ring = modeled_a2a_ring_time(slow, 1 << 20, 4 << 20, 8,
                                     t.hop_chunks)
        one = modeled_oneshot_time(slow, 1 << 20, 4 << 20, 8)
        assert ring < one

    def test_wire_bound_prefers_oneshot(self):
        # the a2a ring's distance-s hops move ~d/2x more link traffic,
        # so a fast decoder must fall back to one-shot
        fast = AlphaBetaModel(decode_Bps=1e13)
        assert choose_a2a_transport(
            1 << 20, 4 << 20, 8, model=fast).kind == "oneshot"

    def test_distance_charging_monotone_in_axis(self):
        m = AlphaBetaModel()
        ts = [modeled_a2a_ring_time(m, 1 << 16, 4 << 16, d)
              for d in (2, 4, 8)]
        assert ts[0] < ts[1] < ts[2]


class TestCalibration:
    def test_calibrate_moe_entries(self):
        from repro.comm import calibrate_moe_entries
        from repro.core.registry import CodecRegistry
        from repro.models import init_params
        cfg = reduced(get_config("deepseek-moe-16b"))
        params = init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "labels": tok}
        reg = CodecRegistry()
        entries = calibrate_moe_entries(reg, cfg, params, batch,
                                        chunk_symbols=256)
        assert set(entries) == {"moe/dispatch", "moe/combine"}
        for e in entries.values():
            assert 0 < e.plan.expected_bits_per_symbol <= 8.0
        # idempotent: names already registered are kept as-is
        again = calibrate_moe_entries(reg, cfg, params, batch,
                                      chunk_symbols=256)
        assert all(again[n].scheme_id == entries[n].scheme_id
                   for n in entries)


MD_PARITY = r"""
import contextlib
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import moe
from repro.parallel import sharding as shd
from repro.core.registry import CodecRegistry
from repro.comm.channel import Channel, ChannelSpec
from repro.comm.calibrate import histogram_of_quantized

cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                  num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                  moe=MoEConfig(num_experts=4, top_k=2, d_expert=8,
                                num_shared_experts=1))
params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
            ("data", "model"))

def with_impl(c, impl, **over):
    return dataclasses.replace(
        c, moe=dataclasses.replace(c.moe, impl=impl, **over))

buf, out_e = moe.dispatch_traffic(params, x, cfg)
reg = CodecRegistry()
reg.register("moe/dispatch",
              np.maximum(histogram_of_quantized(buf), 1e-6),
              chunk_symbols=256)
reg.register("moe/combine",
              np.maximum(histogram_of_quantized(out_e), 1e-6),
              chunk_symbols=256)

def chans(transport, enabled=True):
    out = {}
    for name in (moe.MOE_DISPATCH, moe.MOE_COMBINE):
        ch = Channel(ChannelSpec(codec=name, transport=transport,
                                 axis="model", axis_size=4),
                     registry=reg)
        if not enabled:
            ch = Channel(ChannelSpec(
                codec=name, transport=transport,
                cfg=dataclasses.replace(ch.cfg, enabled=False),
                axis="model", axis_size=4), registry=reg)
        out[name] = ch
    return out

def run(c, channels=None):
    ctx = (moe.bind_moe_channels(channels) if channels
           else contextlib.nullcontext())
    with shd.use_mesh(mesh), ctx:
        return np.asarray(
            jax.jit(lambda p, t: moe.moe_block(p, t, c))(params, x))

# 1) uncompressed parity, shared-experts path included, same mesh
y_g = run(cfg)
y_raw = run(with_impl(cfg, "shardmap_a2a"))
assert (y_raw == y_g).all(), "raw shardmap_a2a != gspmd bitwise"

# 2) capacity-overflow drop determinism (cf=0.25 forces drops)
c_of = dataclasses.replace(
    cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
y_gof = run(c_of)
y_aof = run(with_impl(c_of, "shardmap_a2a"))
assert (y_aof == y_gof).all(), "overflow drops differ"
# the tiny capacity really dropped assignments (outputs change)
assert (y_gof != y_g).any(), "cf=0.25 dropped nothing -- test is vacuous"

# 3) compressed wire: lossless vs its raw-e4m3 twin, ring == oneshot,
#    auto resolves consistently, and e4m3-level closeness to gspmd
c_a = with_impl(cfg, "shardmap_a2a")
y_c1 = run(c_a, chans("oneshot"))
y_off = run(c_a, chans("oneshot", enabled=False))
assert (y_c1 == y_off).all(), "QLC wire != raw-e4m3 twin (lossy!)"
y_cr = run(c_a, chans("ring"))
assert (y_cr == y_c1).all(), "ring a2a != one-shot a2a"
y_auto = run(c_a, chans("auto"))
assert (y_auto == y_c1).all(), "auto transport changed numerics"
rel = np.linalg.norm(y_c1 - y_g) / np.linalg.norm(y_g)
assert rel < 0.15, f"compressed vs gspmd rel l2 {rel}"
assert rel > 0, "compressed output identical to f32 -- not quantizing?"

# 4) grouped_local agrees bitwise at one dispatch group
y_grp = run(with_impl(cfg, "grouped_local", dispatch_groups=1))
assert (y_grp == y_g).all(), "grouped_local(1) != gspmd"

# 5) gradients: raw a2a close to gspmd (backward graphs differ, so
#    allclose not bitwise); compressed grads finite + nonzero through
#    the custom_vjp (raw a2a backward)
def loss(c, channels=None):
    def f(p):
        ctx = (moe.bind_moe_channels(channels) if channels
               else contextlib.nullcontext())
        with ctx:
            return jnp.sum(moe.moe_block(p, x, c) ** 2)
    return f

with shd.use_mesh(mesh):
    g_g = jax.jit(jax.grad(loss(cfg)))(params)
    g_raw = jax.jit(jax.grad(loss(c_a)))(params)
    g_c = jax.jit(jax.grad(loss(c_a, chans("oneshot"))))(params)
flat_g = jax.tree_util.tree_leaves_with_path(g_g)
flat_raw = jax.tree.leaves(g_raw)
assert len(flat_g) == len(flat_raw)
for (path, leaf_g), leaf_raw in zip(flat_g, flat_raw):
    np.testing.assert_allclose(np.asarray(leaf_raw), np.asarray(leaf_g),
                               rtol=1e-5, atol=1e-6,
                               err_msg=jax.tree_util.keystr(path))
for path, v in jax.tree_util.tree_leaves_with_path(g_c):
    assert bool(jnp.isfinite(v).all()), \
        f"nonfinite compressed grad {jax.tree_util.keystr(path)}"
assert any(bool((v != 0).any()) for v in jax.tree.leaves(g_c))
print("MOE_PARITY_OK")
"""


def test_shardmap_a2a_parity_multidevice():
    out = run_md(MD_PARITY, n_devices=8)
    assert "MOE_PARITY_OK" in out
