"""Online codec adaptation, end to end: telemetry -> drift -> hot-swap.

A compressed all-gather channel runs over the "data" axis while the
activation distribution SHIFTS mid-run (Gaussian -> post-nonlinearity
zero spike, the paper's §6 Table 1 vs Table 2 scenario). The fused
encode pass's histogram side output feeds a TrafficMonitor; the
DriftPolicy flags the mismatch; the Recalibrator re-runs scheme
selection + empirical plan sizing on the accumulated histogram and the
controller hot-swaps the channel to a NEW scheme-id.

Verified here (and gated in CI):
* a container encoded under the OLD scheme-id decodes bit-exactly
  after the swap — old registry entries are retained, never mutated;
* the post-shift measured bits/symbol under the swapped codec is
  within 5% of a FRESH calibration on the shifted distribution.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/online_adaptation.py
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.adaptive import AdaptiveController, DriftConfig
from repro.comm import container as qc
from repro.comm.calibrate import calibrate_for_tensor
from repro.comm.channel import Channel, ChannelSpec
from repro.core import CodecRegistry
from repro.parallel import sharding as shd

N_PER_DEV = 16384
SHIFT_STEP = 4
STEPS = 14
CHUNK = 512


def batch(step: int, n_dev: int) -> np.ndarray:
    """Per-device activation rows; the distribution shifts at
    SHIFT_STEP from smooth Gaussian to a 40% zero spike (a relu-like
    dominant-symbol stream the startup codec is mis-matched to)."""
    rng = np.random.default_rng(100 + step)
    x = rng.normal(0.0, 1.0, size=(n_dev, N_PER_DEV)).astype(np.float32)
    if step >= SHIFT_STEP:
        x[rng.random(size=x.shape) < 0.4] = 0.0
    return x


def main():
    n_dev = len(jax.devices())
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))

    # Startup calibration on the PRE-shift distribution.
    registry = CodecRegistry()
    tables, plan = calibrate_for_tensor(
        jnp.asarray(batch(0, n_dev).reshape(-1)), chunk_symbols=CHUNK)
    entry_a = registry.register_tables("acts", tables, plan)
    print(f"startup codec: scheme-id {entry_a.scheme_id}, "
          f"{plan.expected_bits_per_symbol:.2f} bits/sym expected")

    ctl = AdaptiveController(
        registry,
        drift=DriftConfig(min_events=2, hysteresis=2, cooldown=2,
                          min_symbols=4096))
    ach = ctl.wrap(Channel(ChannelSpec(codec="acts", axis="data",
                                       axis_size=n_dev),
                           registry=registry))

    # An in-flight container under the startup scheme-id, decoded now
    # as the bit-exactness reference.
    ref_values = batch(1, n_dev)[0]
    ref_container = qc.encode_values(ref_values, entry_a)
    ref_decoded, ok, _ = qc.decode_values(ref_container, registry)
    assert bool(ok)
    ref_decoded = np.asarray(ref_decoded)

    def make_roundtrip(channel):
        # The channel binding is captured at TRACE time — rebuilt after
        # every hot-swap, exactly like a jitted train step would be.
        def body(x):
            vals, ok, hist = channel.all_gather(x.reshape(-1),
                                                with_hist=True)
            return (vals.reshape(n_dev, -1),
                    jax.lax.psum(jnp.int32(0), "data") + jnp.int32(ok),
                    jax.lax.psum(hist, "data"))
        return jax.jit(shd.shard_map(
            body, mesh=mesh, in_specs=(P("data"),),
            out_specs=(P("data"), P(), P())))

    roundtrip = make_roundtrip(ach)
    swap_steps = []
    for step in range(STEPS):
        x = jnp.asarray(batch(step, n_dev))
        _vals, _ok, hist = roundtrip(x)
        ctl.observe("acts", np.asarray(hist))
        events = ctl.check()
        for ev in events:
            swap_steps.append(step)
            print(f"step {step}: hot-swap scheme-id {ev.old_scheme_id} "
                  f"-> {ev.new_scheme_id} ({ev.measured_bits:.2f} "
                  f"measured vs {ev.old_expected_bits:.2f} planned "
                  f"bits/sym; new plan {ev.new_expected_bits:.2f})")
            roundtrip = make_roundtrip(ach)
        m = ctl.monitor.measured_bits("acts")
        if m is not None:
            print(f"step {step:2d}: scheme-id "
                  f"{registry['acts'].scheme_id}, "
                  f"{m:.2f} measured bits/sym")

    assert swap_steps, "drift never triggered a hot-swap"
    assert registry["acts"].scheme_id != entry_a.scheme_id

    # (a) Old in-flight containers decode bit-exactly after the swap.
    post, ok, _ = qc.decode_values(ref_container, registry)
    assert bool(ok)
    assert np.array_equal(np.asarray(post), ref_decoded), \
        "old-scheme container changed after hot-swap"
    print(f"old scheme-id {entry_a.scheme_id} container: bit-exact "
          "after swap")

    # (c) Recovered bits/symbol vs a fresh calibration on the shifted
    # distribution.
    adapted = ctl.monitor.measured_bits("acts")
    _t2, fresh_plan = calibrate_for_tensor(
        jnp.asarray(batch(STEPS, n_dev).reshape(-1)),
        chunk_symbols=CHUNK)
    ratio = adapted / fresh_plan.expected_bits_per_symbol
    print(f"adapted {adapted:.3f} vs fresh "
          f"{fresh_plan.expected_bits_per_symbol:.3f} bits/sym "
          f"(ratio {ratio:.3f})")
    assert ratio <= 1.05, f"adaptation did not recover: {ratio:.3f}"
    print("OK")


if __name__ == "__main__":
    main()
