"""Logical-axis sharding rules (MaxText-style) + helpers.

Every parameter/activation dimension gets a *logical* name; a rule table
maps logical names to mesh axes. Changing the parallelism layout (or
pod count) only changes the rules, never the model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

#: Default rules for the production meshes:
#:   single-pod  (16, 16)    axes ("data", "model")
#:   multi-pod   (2, 16, 16) axes ("pod", "data", "model")
#: "fsdp" dims shard params over the data axis (ZeRO-3 style); heads /
#: mlp / experts / vocab shard over the model axis; batch over pod+data.
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "vocab": "model",
    "fsdp": ("pod", "data"),   # parameter sharding dim (first non-sharded)
    "layers": None,
    "kv_seq": None,            # switched to ("data",) for seq-sharded decode
    "state": None,
    "conv": None,
    "blocks32": None,
}


@dataclasses.dataclass
class ShardingRules:
    """Activation rules + parameter-dim overrides (FSDP etc.).

    Specs are divisibility-aware: an axis (or tuple prefix) is only used
    for a dim it divides — e.g. 56 attention heads fall back to
    replicated on a 16-way model axis instead of failing to lower.
    """
    rules: Dict[str, MeshAxes]
    param_overrides: Dict[str, MeshAxes] = dataclasses.field(
        default_factory=dict)

    def _resolve(self, name: Optional[str], dim: Optional[int],
                 mesh, param: bool, used: set) -> MeshAxes:
        if name is None:
            return None
        ax = (self.param_overrides.get(name, self.rules.get(name))
              if param else self.rules.get(name))
        if ax is None:
            return None
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        if mesh is not None:
            axes = tuple(a for a in axes if a in mesh.axis_names)
        # a mesh axis may appear at most once per spec: first dim wins
        axes = tuple(a for a in axes if a not in used)
        if dim is not None and mesh is not None:
            # keep the maximal prefix whose total size divides the dim
            kept = []
            prod = 1
            for a in axes:
                size = mesh.shape[a]
                if dim % (prod * size) == 0:
                    kept.append(a)
                    prod *= size
                else:
                    break
            axes = tuple(kept)
        if not axes:
            return None
        used.update(axes)
        return axes[0] if len(axes) == 1 else axes

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None,
             param: bool = False) -> P:
        mesh = _current_mesh()
        dims = list(shape) if shape is not None else [None] * len(
            logical_axes)
        # Axes that are Manual in the current trace (inside shard_map)
        # cannot appear in sharding constraints — treat them as taken.
        used: set = set(_manual_axes())
        parts = [self._resolve(name, d, mesh, param, used)
                 for name, d in zip(logical_axes, dims)]
        return P(*parts)


_STATE = threading.local()


def set_rules(rules: Optional[ShardingRules]):
    _STATE.rules = rules


def get_rules() -> ShardingRules:
    r = getattr(_STATE, "rules", None)
    return r if r is not None else ShardingRules(dict(DEFAULT_RULES))


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Enter a mesh context (framework-tracked + jax ``with mesh:``)."""
    old = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _STATE.mesh = old


def _abstract_mesh():
    try:
        return jax.sharding.get_abstract_mesh()
    except Exception:
        return None


def _current_mesh() -> Optional[Mesh]:
    m = getattr(_STATE, "mesh", None)
    if m is not None:
        return m
    am = _abstract_mesh()
    if am is not None and am.axis_names:
        return am
    return None


def _manual_axes() -> frozenset:
    """Mesh axes currently under manual (shard_map) control."""
    am = _abstract_mesh()
    if am is None or not am.axis_names:
        return frozenset()
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == jax.sharding.AxisType.Manual)


def shard_map(f, *, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` without replication checking.

    Replication checking must stay off: the compressed collectives can
    run Pallas kernels, which have no replication rule.
    ``manual_axes=None`` means fully manual over every mesh axis.
    """
    kw = {"check_vma": False}
    if manual_axes is not None:
        kw["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def logical_constraint(x: jax.Array, logical_axes: Sequence[Optional[str]]
                       ) -> jax.Array:
    """with_sharding_constraint by logical axis names (no-op off-mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = get_rules().spec(logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def is_spec_leaf(s) -> bool:
    return isinstance(s, tuple) and all(
        x is None or isinstance(x, str) for x in s)


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   shape: Optional[Sequence[int]] = None,
                   param: bool = False) -> NamedSharding:
    with use_mesh(mesh):
        return NamedSharding(
            mesh, get_rules().spec(logical_axes, shape=shape, param=param))


def param_sharding(mesh: Mesh, specs_tree, shapes_tree):
    """Logical-axis tuples + leaf shapes -> NamedShardings (param rules)."""
    return jax.tree.map(
        lambda spec, leaf: named_sharding(
            mesh, spec, shape=leaf.shape, param=True),
        specs_tree, shapes_tree, is_leaf=is_spec_leaf)


#: FSDP parameter overrides: shard the param 'embed'/'mlp-in' dims over
#: the dp axes (ZeRO-3-style); activations keep embed replicated.
FSDP_PARAM_OVERRIDES: Dict[str, MeshAxes] = {
    "embed": ("pod", "data"),
}


def make_rules(fsdp_params: bool = True, decode_seq_shard: bool = False,
               extra: Optional[Dict[str, MeshAxes]] = None) -> ShardingRules:
    rules = dict(DEFAULT_RULES)
    if decode_seq_shard:
        # long-context decode with tiny batch: shard the KV cache /
        # sequence dim instead of batch.
        rules["kv_seq"] = ("data",)
        rules["batch"] = None
    if extra:
        rules.update(extra)
    return ShardingRules(
        rules=rules,
        param_overrides=dict(FSDP_PARAM_OVERRIDES) if fsdp_params else {})
