"""Mesh definitions.

Functions (never module-level constants) so importing this module
never touches jax device state. ``make_device_mesh`` shapes the devices
present, which is what the launchers run on. ``make_production_mesh``
describes the 256-chip pod (16 data, 16 model; multi-pod adds a
leading "pod" axis) for the compile-only dry run.
The "pod" axis is the DCN tier: the hierarchical transport
(``ChannelSpec(pod_axis="pod")``) rings over "data" within a pod and
bridges pods with one compressed exchange per hop group.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, pods: int = None):
    """The 256-chip single-pod mesh, or a pod-major multi-pod one.

    ``pods`` sets the leading "pod" axis size explicitly (``--pods``);
    ``multi_pod`` is the legacy 2-pod switch. Device order is pod-major
    so the combined (pod, data) rank ``q * 16 + l`` matches the
    channel layer's pod-major convention.
    """
    if pods is None:
        pods = 2 if multi_pod else 1
    shape = (pods, 16, 16) if pods > 1 else (16, 16)
    axes = ("pod", "data", "model") if pods > 1 else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_device_mesh(*, devices=None, model: int = 2, pods: int = 1):
    """A (data, model) mesh over the devices present — one chip, a
    four-chip host, or fake CPU devices in tests. The launchers' mesh.

    ``pods > 1`` simulates a multi-host topology on fake devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``): the
    device grid gains a leading "pod" axis, e.g. 8 CPU devices with
    ``pods=2, model=2`` make a (2, 2, 2) pod x data x model mesh.
    """
    import numpy as np
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    model = min(model, n)
    pods = max(1, int(pods))
    data = n // (model * pods)
    if data < 1:
        raise ValueError(
            f"{n} devices cannot shape a pods={pods} x model={model} "
            "mesh with a non-empty data axis")
    if pods > 1:
        return jax.sharding.Mesh(
            np.array(devs[:pods * data * model]).reshape(
                pods, data, model),
            ("pod", "data", "model"))
    return jax.sharding.Mesh(
        np.array(devs[:data * model]).reshape(data, model),
        ("data", "model"))
