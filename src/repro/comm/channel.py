"""Unified ``Channel`` API: bind codec + transport + mesh axis ONCE.

The paper's deployment model (one LUT per tensor type, §7) and the
transport layer both imply a *binding* — codec entry x transport plan x
mesh axis — yet the pre-channel entry points re-accepted it as loose
kwargs (``tables, cfg=None, *, transport=None, axis_size=None``) with
resolution logic duplicated across the collectives, the train step, the
weight wire, and serving. A :class:`Channel` makes that decision once:

    reg = CodecRegistry(); reg.register("grads", counts)
    ch = Channel(ChannelSpec(codec="grads", transport="auto",
                             axis="data", axis_size=8), registry=reg)
    seg, valid, ok = ch.reduce_scatter(g)      # inside shard_map
    full, ok = ch.all_gather(seg)

The channel is immutable: every wire decision (tables, wire config,
transport policy, axis placement, kernel toggle) is resolved and
validated at construction — a ring transport without a static
``axis_size`` is a construction-time ``ValueError``, not a mid-trace
surprise — and the four collectives plus the local
``compress``/``decompress`` transforms are methods, so nothing is
re-resolved per call. The one *per-call* decision left is the
``"auto"`` transport policy: payload sizes are only static at trace
time, so ``resolved_transport`` picks one-shot vs ring (and clamps
ring hop chunking to tile the payload) from each call's static
geometry — this is what used to be ``train_step._auto_axis_transports``.

``Channel.autotune`` closes the ROADMAP "autotuned hop size" item: it
measures this host's decode throughput on a representative payload of
the channel's own codec (the ``benchmarks/transport_overlap`` beta_decode
measurement, packaged as :func:`measure_decode_Bps`) and — given a
``mesh`` — the per-axis WIRE bandwidth (:func:`measure_wire_Bps`, one
timed ppermute per axis), feeds both to the planner's per-link-class
alpha-beta model, and caches the tuned
:class:`~repro.comm.planner.TransportConfig` in the channel's
:class:`~repro.core.registry.CodecRegistry` keyed by
``(scheme_id, axis, payload bucket, is_reduce)`` plus the measured
link constants per axis (``cache_link_constants``). Both caches
serialize with the
registry JSON, so a reloaded registry reuses the tuning — and any
channel with ``transport="auto"`` bound to that registry picks it up
before falling back to the modeled choice.

``open_channels(registry, mesh, ...)`` builds the per-tensor-type
``{name: Channel}`` map in one call. Multi-host DCN-tier transport is
the ``ChannelSpec(pod_axis=..., pod_axis_size=...)`` binding: the
collectives then run over the combined pod x local group (pod-major
rank order) and the ``hierarchical`` transport rings within the pod
while bridging pods with one compressed exchange per hop group
(``repro.comm.transport``).

The legacy functional API (``qlc_*``, ``compress_values``, ...) remains
as thin deprecated wrappers over one-shot channels — bit-identical
outputs, ``DeprecationWarning`` on call.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import compressed as comp
from repro.comm.planner import (AlphaBetaModel, ONESHOT, TransportConfig,
                                choose_a2a_transport, choose_transport,
                                clamp_hop_chunks, payload_wire_bytes)

#: sentinel transport policy: resolve per call from static payload
#: geometry (registry cache first, then the planner's alpha-beta model).
AUTO = "auto"


@dataclasses.dataclass(frozen=True)
class ChannelSpec:
    """Declarative channel binding: codec x transport x mesh axis.

    ``codec``
        What compresses the wire: a registry key (``str``, resolved
        against the registry the channel is opened with), a
        :class:`~repro.core.registry.CodecEntry`, a bare
        :class:`~repro.core.lut.CodecTables` (requires ``cfg``), or
        ``None`` — the registry's ``"default"``/first entry.
    ``cfg``
        Explicit :class:`~repro.comm.compressed.CommConfig`. Optional
        with an entry (derived from its calibrated plan); required with
        bare tables.
    ``transport``
        ``None``/``"oneshot"`` (legacy single collective), ``"ring"``
        (ppermute pipeline), ``"hierarchical"`` (intra-pod ring +
        compressed inter-pod bridge; needs ``pod_axis`` to differ from
        ring), ``"auto"`` (planner/registry-cache choice per call), or
        a concrete :class:`~repro.comm.planner.TransportConfig`.
    ``axis`` / ``axis_size``
        The mesh axis the collectives run over and its static size.
        Ring, hierarchical and auto transports REQUIRE ``axis_size``
        (the hop loop is unrolled at trace time) — validated at
        construction.
    ``pod_axis`` / ``pod_axis_size``
        Optional second (slow, DCN-tier) mesh axis. When bound, the
        collectives run over the combined ``pod_axis_size x axis_size``
        group in pod-major rank order (``g = pod_index * axis_size +
        local_index``) and ``axis``/``axis_size`` keep describing the
        LOCAL (fast, ICI) axis. ``"ring"`` cannot run over a pod-bound
        channel (validated at construction); ``"hierarchical"`` without
        a pod axis degrades to ``"ring"``.
    ``use_kernels`` / ``enabled`` / ``scale_dtype``
        Non-plan wire knobs; ``None`` keeps the codec's defaults.
    """
    codec: Any = None
    cfg: Optional["comp.CommConfig"] = None
    transport: Any = None
    axis: Optional[str] = None
    axis_size: Optional[int] = None
    pod_axis: Optional[str] = None
    pod_axis_size: Optional[int] = None
    use_kernels: Optional[bool] = None
    enabled: Optional[bool] = None
    scale_dtype: Optional[str] = None

    def cfg_overrides(self) -> Dict[str, Any]:
        return {k: v for k, v in (("use_kernels", self.use_kernels),
                                  ("enabled", self.enabled),
                                  ("scale_dtype", self.scale_dtype))
                if v is not None}


def _resolve_transport_policy(transport):
    """``ChannelSpec.transport`` -> TransportConfig or the AUTO sentinel."""
    if transport is None:
        return ONESHOT
    if isinstance(transport, TransportConfig):
        return transport
    if isinstance(transport, str):
        if transport == AUTO:
            return AUTO
        return TransportConfig(kind=transport)     # validates the kind
    raise TypeError(f"bad transport spec: {transport!r}")


class Channel:
    """Immutable bound wire: codec + transport policy + mesh axis.

    Construct from a :class:`ChannelSpec` (plus the registry supplying
    named codecs and the autotune cache); all resolution and validation
    happens here, once. Collective methods (``all_gather``,
    ``reduce_scatter``, ``psum``, ``all_to_all``) must be called inside
    ``shard_map`` with ``spec.axis`` manual, exactly like the legacy
    ``qlc_*`` functions; ``compress``/``decompress``/``wire_bytes``
    are local and need no mesh.
    """

    __slots__ = ("spec", "registry", "entry", "tables", "cfg", "model",
                 "_transport")

    def __init__(self, spec: ChannelSpec, *, registry=None, model=None):
        from repro.core.lut import CodecTables
        from repro.core.registry import CodecEntry, CodecRegistry

        if registry is not None and not isinstance(registry, CodecRegistry):
            raise TypeError(f"registry must be a CodecRegistry, got "
                            f"{type(registry).__name__}")

        codec = spec.codec
        entry = None
        if isinstance(codec, str):
            if registry is None:
                raise TypeError(
                    f"codec {codec!r} is a registry key but the channel "
                    "has no registry; pass Channel(spec, registry=...)")
            entry = registry[codec]
        elif isinstance(codec, CodecEntry):
            entry = codec
        elif codec is None:
            if registry is None:
                raise TypeError(
                    "ChannelSpec.codec is None and no registry given; "
                    "name a codec or bind a registry with entries")
            entry = registry.get("default")
            if entry is None:
                entries = registry.entries()
                if not entries:
                    raise TypeError("empty codec registry")
                entry = entries[0]

        if entry is not None:
            tables = entry.tables
            cfg = spec.cfg
            if cfg is None:
                cfg = entry.config(**spec.cfg_overrides())
            elif spec.cfg_overrides():
                cfg = dataclasses.replace(cfg, **spec.cfg_overrides())
        elif isinstance(codec, CodecTables):
            if spec.cfg is None:
                raise TypeError(
                    "a bare CodecTables needs an explicit CommConfig; "
                    "pass ChannelSpec(cfg=...) or a registry CodecEntry")
            tables = codec
            cfg = dataclasses.replace(spec.cfg, **spec.cfg_overrides()) \
                if spec.cfg_overrides() else spec.cfg
        else:
            raise TypeError(f"bad codec spec: {codec!r}")

        transport = _resolve_transport_policy(spec.transport)
        kind = AUTO if transport == AUTO else transport.kind
        if kind in ("ring", "hierarchical") and spec.axis is None:
            raise ValueError(
                f"the {kind!r} transport needs a mesh axis; pass "
                "ChannelSpec(axis=..., axis_size=...)")
        if kind in ("ring", "hierarchical", AUTO) and spec.axis is not None \
                and spec.axis_size is None:
            raise ValueError(
                f"the {kind!r} transport needs the static axis_size "
                f"(the ring hop loop is unrolled at trace time); pass "
                f"ChannelSpec(axis={spec.axis!r}, "
                f"axis_size=mesh.shape[{spec.axis!r}])")
        if spec.axis_size is not None and spec.axis_size < 1:
            raise ValueError(f"axis_size must be >= 1, got "
                             f"{spec.axis_size}")
        if spec.pod_axis is not None:
            if spec.pod_axis == spec.axis:
                raise ValueError(
                    f"pod_axis {spec.pod_axis!r} must differ from the "
                    "local axis")
            if spec.pod_axis_size is None:
                raise ValueError(
                    "a pod-bound channel needs the static "
                    "pod_axis_size (the bridge loop is unrolled at "
                    f"trace time); pass ChannelSpec(pod_axis="
                    f"{spec.pod_axis!r}, "
                    f"pod_axis_size=mesh.shape[{spec.pod_axis!r}])")
            if spec.pod_axis_size < 1:
                raise ValueError(f"pod_axis_size must be >= 1, got "
                                 f"{spec.pod_axis_size}")
            if kind == "ring" and spec.pod_axis_size > 1:
                raise ValueError(
                    "kind='ring' is a single-axis neighbor ring and "
                    "cannot run over a pod-bound channel; use "
                    "'oneshot', 'hierarchical', or 'auto'")
        elif spec.pod_axis_size not in (None, 1):
            raise ValueError("pod_axis_size without pod_axis")

        object.__setattr__(self, "spec", spec)
        object.__setattr__(self, "registry", registry)
        object.__setattr__(self, "entry", entry)
        object.__setattr__(self, "tables", tables)
        object.__setattr__(self, "cfg", cfg)
        object.__setattr__(self, "model", model)
        object.__setattr__(self, "_transport", transport)

    def __setattr__(self, name, value):
        raise AttributeError(
            f"Channel is immutable; use channel.replace({name}=...)")

    def __repr__(self):
        t = self._transport
        t = t if t == AUTO else t.kind
        name = self.entry.name if self.entry is not None else "<tables>"
        return (f"Channel(codec={name!r}, transport={t!r}, "
                f"axis={self.axis!r}, axis_size={self.axis_size})")

    # ---- placement / policy ---------------------------------------------

    @property
    def axis(self) -> Optional[str]:
        return self.spec.axis

    @property
    def axis_size(self) -> Optional[int]:
        return self.spec.axis_size

    @property
    def pod_axis(self) -> Optional[str]:
        return self.spec.pod_axis

    @property
    def pod_size(self) -> int:
        """Pod-axis size (1 on a flat, single-tier channel)."""
        if self.spec.pod_axis is None:
            return 1
        return int(self.spec.pod_axis_size)

    @property
    def group_size(self) -> Optional[int]:
        """Total collective group size: ``pod_size * axis_size``."""
        if self.axis_size is None:
            return None
        return self.pod_size * int(self.axis_size)

    def _pod_kw(self) -> Dict[str, Any]:
        if self.spec.pod_axis is None or self.pod_size <= 1:
            return {}
        return {"pod_axis": self.spec.pod_axis, "pod_size": self.pod_size}

    @property
    def transport(self):
        """The bound policy: a ``TransportConfig`` or ``"auto"``."""
        return self._transport

    def replace(self, **spec_changes) -> "Channel":
        """New channel with updated spec fields (same registry/model)."""
        return Channel(dataclasses.replace(self.spec, **spec_changes),
                       registry=self.registry, model=self.model)

    def _require_axis(self) -> str:
        if self.axis is None:
            raise ValueError(
                "this channel has no mesh axis bound; collectives need "
                "ChannelSpec(axis=...)")
        return self.axis

    def resolved_transport(self, n_values: int, *, is_reduce: bool = False,
                           axis_size: Optional[int] = None,
                           is_a2a: bool = False) -> TransportConfig:
        """Concrete transport for one collective call.

        ``n_values`` is this shard's f32 value count entering the
        collective (static at trace time). The ``"auto"`` policy first
        consults the registry's autotune cache (``(scheme_id, axis,
        payload bucket, is_reduce)`` — see :meth:`autotune`), then
        falls back to the planner's alpha-beta model; one-shot
        reduce-scatter is charged its ``axis_size`` accumulate
        dispatches (ring-parity op sequence) on both paths. Ring hop
        chunking is clamped to tile the per-shard chunk count so hop
        padding can never change the payload's static segment geometry.

        ``is_a2a=True`` (``n_values`` = one destination ROW) resolves
        through the planner's distance-charged a2a model instead —
        all-gather-tuned cache entries don't transfer to the a2a's
        ppermute schedule, so the cache is skipped.

        On a pod-bound channel ``axis_size`` is the LOCAL size; the
        reduce unit divides by the combined group size, the cost model
        is the per-link-class one (axis constants from the registry's
        link cache when probed — :meth:`autotune`), and the candidates
        are one-shot vs hierarchical (a flat ring cannot run over a
        two-axis group).
        """
        d = int(axis_size if axis_size is not None
                else (self.axis_size or 1))
        P = self.pod_size
        k = self.cfg.chunk_symbols
        unit = -(-int(n_values) // (d * P)) if is_reduce else int(n_values)
        t = self._transport
        if t == AUTO:
            t = None
            if not is_a2a and self.registry is not None \
                    and self.entry is not None and self.axis is not None:
                t = self.registry.cached_transport(
                    self.entry.scheme_id, self.axis, 4 * unit,
                    is_reduce=is_reduce)
            if t is None:
                wire = payload_wire_bytes(unit, k, self.cfg.capacity_words,
                                          self.cfg.pool_slots_per_1k)
                model = self._linked_model()
                if is_a2a and P == 1:
                    t = choose_a2a_transport(wire, 4.0 * unit, d,
                                             model=model)
                else:
                    t = choose_transport(
                        wire, 4.0 * unit, d, model=model, pod_size=P,
                        n_oneshot_decode_dispatches=(d * P if is_reduce
                                                     else 1))
        if t.kind in ("ring", "hierarchical"):
            n_chunks = max(1, -(-unit // k))
            t = dataclasses.replace(
                t, hop_chunks=clamp_hop_chunks(t.hop_chunks, n_chunks))
        return t

    def _linked_model(self, base: Optional[AlphaBetaModel] = None
                      ) -> AlphaBetaModel:
        """The channel's cost model with any MEASURED per-axis link
        constants from the registry's link cache folded in
        (``CodecRegistry.cache_link_constants`` — written by
        :meth:`autotune`'s wire probe)."""
        m = base or self.model or AlphaBetaModel()
        if self.registry is None:
            return m
        for ax in (self.axis, self.spec.pod_axis):
            if ax is None:
                continue
            e = self.registry.cached_link_constants(ax)
            if e is not None:
                m = m.with_link(e["link"], wire_Bps=e["wire_Bps"],
                                alpha_s=e["alpha_s"])
        return m

    # ---- local wire transforms ------------------------------------------

    def compress(self, x: jnp.ndarray, *, with_hist: bool = False):
        """float [..., M] (M % chunk_symbols == 0) -> (payload, scales).

        ``with_hist=True`` appends the i32[256] encoded-symbol
        histogram (fused into the encode kernel — the
        ``repro.adaptive`` telemetry tap)."""
        return comp._compress_values(x, self.tables, self.cfg,
                                     emit_hist=with_hist)

    def decompress(self, payload: "comp.WirePayload", scales: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(payload, scales) -> (float32 values, ok)."""
        return comp._decompress_values(payload, scales, self.tables,
                                       self.cfg)

    def compress_codes(self, codes: jnp.ndarray) -> "comp.WirePayload":
        """uint8 symbols [..., M] -> payload (no quantization)."""
        return comp._compress_codes(codes, self.tables, self.cfg)

    def decompress_codes(self, payload: "comp.WirePayload"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """payload -> (uint8 symbols, ok)."""
        return comp._decompress_codes(payload, self.tables, self.cfg)

    def wire_bytes(self, payload: "comp.WirePayload",
                   scales: Optional[jnp.ndarray] = None) -> int:
        """Static wire footprint of a payload (+ scales) in bytes."""
        return comp.wire_bytes(payload, scales)

    def modeled_wire_bytes(self, n_values: int,
                           hop_chunks: int = 1) -> int:
        """Static wire bytes of an ``n_values``-value payload — the
        planner-side mirror of :meth:`wire_bytes`, no arrays needed.
        ``hop_chunks > 1`` charges the ring piece split's per-piece
        row-sized escape pools (the ok-parity wire shape)."""
        return payload_wire_bytes(int(n_values), self.cfg.chunk_symbols,
                                  self.cfg.capacity_words,
                                  self.cfg.pool_slots_per_1k,
                                  hop_chunks=hop_chunks)

    # ---- collectives (call inside shard_map over spec.axis) -------------

    def all_gather(self, x: jnp.ndarray, *, with_hist: bool = False):
        """All-gather this shard's float payload. Returns
        ``(gathered f32 [group_size * x.size], ok)`` — rows in
        pod-major rank order on a pod-bound channel; ``with_hist``
        appends this shard's encoded-symbol histogram i32[256]."""
        from repro.comm import transport as tr
        axis = self._require_axis()
        t = self.resolved_transport(x.size)
        flat, n = comp.pad_to_multiple(
            x, t.hop_chunks * self.cfg.chunk_symbols)
        out = tr.exchange_all_gather(
            flat, axis, self.tables, self.cfg, t, self.axis_size,
            emit_hist=with_hist, **self._pod_kw())
        vals, ok = out[0], out[1]
        if with_hist:
            return vals[:, :n].reshape(-1), ok, out[2]
        return vals[:, :n].reshape(-1), ok

    def reduce_scatter(self, x: jnp.ndarray, *, with_hist: bool = False):
        """Reduce-scatter(sum). Returns ``ReduceScatterResult(segment,
        valid, ok)`` — segment padded to the static length, ``valid``
        counting its real entries. ``with_hist`` appends the i32[256]
        histogram of every symbol this device encoded."""
        from repro.comm import transport as tr
        axis = self._require_axis()
        if self.axis_size is None:
            raise ValueError(
                "reduce_scatter needs the static axis_size; pass "
                "ChannelSpec(axis_size=mesh.shape[axis])")
        d = int(self.axis_size)
        D = d * self.pod_size
        t = self.resolved_transport(x.size, is_reduce=True)
        flat, n = comp.pad_to_multiple(
            x, D * t.hop_chunks * self.cfg.chunk_symbols)
        seg = flat.shape[0] // D
        xs = flat.reshape(D, seg)
        out = tr.exchange_reduce_scatter(
            xs, axis, d, self.tables, self.cfg, t, emit_hist=with_hist,
            **self._pod_kw())
        acc, ok = out[0], out[1]
        idx = jax.lax.axis_index(axis).astype(jnp.int32)
        if self.spec.pod_axis is not None and self.pod_size > 1:
            idx += jax.lax.axis_index(
                self.spec.pod_axis).astype(jnp.int32) * d
        valid = jnp.clip(jnp.int32(n) - idx * seg, 0, seg)
        res = comp.ReduceScatterResult(segment=acc, valid=valid, ok=ok)
        if with_hist:
            return res, out[2]
        return res

    def psum(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """All-reduce(sum) = compressed RS + compressed AG (both phases
        quantize, as in standard compressed all-reduce; the QLC coding
        adds zero error). The codec is resolved ONCE — here, at channel
        construction — and threaded through both phases."""
        r = self.reduce_scatter(x)
        full, ok_ag = self.all_gather(r.segment)
        out = full[:x.size].reshape(x.shape)
        return out, r.ok & ok_ag

    def all_to_all(self, x: jnp.ndarray, *, with_hist: bool = False):
        """Compressed all-to-all of ``x [D, ...]`` (row j -> peer j).
        ``with_hist`` appends the i32[256] histogram of every symbol
        this device encoded."""
        from repro.comm import transport as tr
        axis = self._require_axis()
        d = x.shape[0]
        P = self.pod_size
        if self.axis_size is not None \
                and int(self.axis_size) * P != d:
            raise ValueError(
                f"all_to_all payload has {d} rows but the channel's "
                f"group size is {int(self.axis_size) * P} "
                f"(axis_size={self.axis_size}, pod_size={P})")
        assert d % P == 0, (d, P)
        row = x.reshape(d, -1)
        n = row.shape[1]
        t = self.resolved_transport(n, axis_size=d // P, is_a2a=True)
        pad = (-n) % (t.hop_chunks * self.cfg.chunk_symbols)
        if pad:
            row = jnp.pad(row, ((0, 0), (0, pad)))
        out = tr.exchange_all_to_all(
            row, axis, self.tables, self.cfg, t, d // P,
            emit_hist=with_hist, **self._pod_kw())
        vals, ok = out[0], out[1]
        if with_hist:
            return vals[:, :n].reshape(x.shape), ok, out[2]
        return vals[:, :n].reshape(x.shape), ok

    # ---- autotune (ROADMAP: autotuned hop size) -------------------------

    def autotune(self, payload_bytes: int, *, is_reduce: bool = False,
                 probe_symbols: int = 1 << 15, repeats: int = 3,
                 model: Optional[AlphaBetaModel] = None,
                 mesh=None, axis_link: str = "ici",
                 wire_probe_bytes: int = 1 << 22) -> "Channel":
        """Measure decode throughput (and, with a ``mesh``, per-axis
        wire bandwidth), pick the transport for a ``payload_bytes``
        per-shard unit, cache it, and return the tuned channel.

        The decode measurement is the ``benchmarks/transport_overlap``
        beta_decode probe (:func:`measure_decode_Bps`) run on a
        representative payload of THIS channel's codec (symbols sampled
        from its calibration histogram). With ``mesh`` given, each of
        the channel's axes is additionally wire-probed with one timed
        ppermute (:func:`measure_wire_Bps`) — the local axis as the
        ``axis_link`` class (``"ici"`` by default; pass ``"dcn"`` for
        a flat channel bound directly on the slow axis), the pod axis
        as ``"dcn"`` — and the
        measured constants land in the registry's link cache
        (``cache_link_constants``), where every later
        :meth:`resolved_transport` (this channel's or any sibling's)
        folds them into the planner model; without a mesh, previously
        cached link constants are still applied.

        ``is_reduce=True`` tunes the reduce-scatter use of the channel
        — the one-shot RS is charged its per-rank accumulate
        dispatches, exactly like :meth:`resolved_transport`'s modeled
        fallback. The tuned
        :class:`~repro.comm.planner.TransportConfig` is cached in the
        channel's registry under ``(scheme_id, axis, payload bucket,
        is_reduce)`` — both caches ride the registry JSON, so a
        reloaded registry reuses the tuning and every
        ``transport="auto"`` channel bound to it resolves to the
        cached config without re-measuring.
        """
        axis = self._require_axis()
        if self.axis_size is None:
            raise ValueError("autotune needs the static axis_size")
        d = int(self.axis_size)
        P = self.pod_size
        counts = None if self.entry is None else self.entry.counts
        decode_Bps, _ = measure_decode_Bps(
            self.tables, self.cfg, probe_symbols, counts=counts,
            repeats=repeats)
        if mesh is not None:
            for ax, link in ((axis, axis_link),
                             (self.spec.pod_axis, "dcn")):
                if ax is None or ax not in mesh.shape \
                        or int(mesh.shape[ax]) < 2:
                    continue
                wire_Bps, _ = measure_wire_Bps(
                    mesh, ax, wire_probe_bytes, repeats=repeats)
                if self.registry is not None:
                    self.registry.cache_link_constants(
                        ax, link, wire_Bps=wire_Bps)
        base = model or self.model or AlphaBetaModel()
        tuned_model = dataclasses.replace(
            self._linked_model(base), decode_Bps=decode_Bps)
        n_values = max(1, int(payload_bytes) // 4)
        t = choose_transport(
            self.modeled_wire_bytes(n_values), float(payload_bytes), d,
            model=tuned_model, pod_size=P,
            n_oneshot_decode_dispatches=d * P if is_reduce else 1)
        if self.registry is not None and self.entry is not None:
            self.registry.cache_transport(
                self.entry.scheme_id, axis, int(payload_bytes), t,
                is_reduce=is_reduce)
        return self.replace(transport=t)


def measure_decode_Bps(tables, cfg, n_symbols: int, *, counts=None,
                       repeats: int = 3, seed: int = 0
                       ) -> Tuple[float, float]:
    """Measure this host's fused decode→dequantize throughput.

    Times the jitted decompress of a payload whose symbols are sampled
    from ``counts`` (the codec's calibration histogram; uniform when
    omitted) — the beta_decode constant of the planner's
    :class:`~repro.comm.planner.AlphaBetaModel`, in decoded f32 value
    bytes per second. Returns ``(decode_Bps, seconds_per_call)``.
    Shared by ``Channel.autotune`` and ``benchmarks/transport_overlap``.
    """
    from repro.quant import e4m3
    k = cfg.chunk_symbols
    m = max(1, int(n_symbols) // k) * k
    rng = np.random.default_rng(seed)
    if counts is None:
        counts = np.ones(256, np.float64)
    pmf = np.maximum(np.asarray(counts, np.float64).reshape(256), 0.0)
    pmf = pmf / pmf.sum()
    syms = rng.choice(256, size=m, p=pmf).astype(np.uint8)
    x = jnp.asarray(np.asarray(e4m3.e4m3_decode(jnp.asarray(syms)),
                               np.float32))
    payload, scales = comp._compress_values(x, tables, cfg)

    dec = jax.jit(
        lambda p, s: comp._decompress_values(p, s, tables, cfg)[0])
    jax.block_until_ready(dec(payload, scales))           # compile
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(dec(payload, scales))
        best = min(best, time.perf_counter() - t0)
    return 4.0 * m / best, best


def measure_wire_Bps(mesh, axis: str, payload_bytes: int = 1 << 22, *,
                     repeats: int = 3) -> Tuple[float, float]:
    """Measure per-hop wire bandwidth over one mesh axis.

    Times a jitted single-hop neighbor ``ppermute`` of a
    ``payload_bytes`` per-device f32 buffer over ``axis`` — the
    alpha-beta model's per-link-class beta_wire constant, in payload
    bytes per second per device. This is how ``Channel.autotune``
    learns that the pod (DCN) axis is slower than the local (ICI) one
    instead of assuming the class defaults in ``roofline.hw``. Returns
    ``(wire_Bps, seconds_per_hop)``.

    On a simulated multi-host mesh (fake CPU devices) the number is a
    memcpy rate, not a network rate — meaningful for exercising the
    plumbing, not for real tuning.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel import sharding as shd
    d = int(mesh.shape[axis])
    if d < 2:
        raise ValueError(f"axis {axis!r} has size {d}; nothing to probe")
    n = max(1, int(payload_bytes) // 4)
    perm = [(j, (j + 1) % d) for j in range(d)]
    spec = PartitionSpec(axis)
    hop = jax.jit(shd.shard_map(
        lambda a: jax.lax.ppermute(a, axis, perm),
        mesh=mesh, in_specs=spec, out_specs=spec))
    x = jax.device_put(jnp.zeros((d, n), jnp.float32),
                       NamedSharding(mesh, spec))
    jax.block_until_ready(hop(x))                         # compile
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(hop(x))
        best = min(best, time.perf_counter() - t0)
    return 4.0 * n / best, best


def open_channels(registry, mesh=None, spec_overrides=None, *,
                  axis: Optional[str] = None,
                  pod_axis: Optional[str] = None,
                  transport: Any = None,
                  use_kernels: Optional[bool] = None,
                  model: Optional[AlphaBetaModel] = None
                  ) -> Dict[str, "Channel"]:
    """Open one :class:`Channel` per registry tensor type.

    Returns ``{name: Channel}`` for every registered name. Defaults
    (``axis``/``pod_axis``/``transport``/``use_kernels``) apply to all
    channels; ``spec_overrides`` maps names to a :class:`ChannelSpec`
    (or a dict of ChannelSpec kwargs) overriding them per type.
    ``axis_size`` / ``pod_axis_size`` are filled in from
    ``mesh.shape`` whenever a spec names an axis without a size.

        channels = open_channels(reg, mesh, axis="data",
                                 transport="auto",
                                 spec_overrides={"params":
                                     {"transport": "oneshot"}})
        seg, valid, ok = channels["grads"].reduce_scatter(g)
    """
    overrides = dict(spec_overrides or {})
    out = {}
    for name in registry.names():
        spec = overrides.get(name)
        if spec is None:
            spec = ChannelSpec(codec=name, transport=transport, axis=axis,
                               pod_axis=pod_axis, use_kernels=use_kernels)
        elif isinstance(spec, dict):
            kw = dict(codec=name, transport=transport, axis=axis,
                      pod_axis=pod_axis, use_kernels=use_kernels)
            kw.update(spec)
            spec = ChannelSpec(**kw)
        elif not isinstance(spec, ChannelSpec):
            raise TypeError(f"spec_overrides[{name!r}] must be a "
                            f"ChannelSpec or dict, got {type(spec).__name__}")
        if spec.codec is None:
            spec = dataclasses.replace(spec, codec=name)
        if spec.axis is not None and spec.axis_size is None \
                and mesh is not None and spec.axis in mesh.shape:
            spec = dataclasses.replace(spec,
                                       axis_size=int(mesh.shape[spec.axis]))
        if spec.pod_axis is not None and spec.pod_axis_size is None \
                and mesh is not None and spec.pod_axis in mesh.shape:
            spec = dataclasses.replace(
                spec, pod_axis_size=int(mesh.shape[spec.pod_axis]))
        out[name] = Channel(spec, registry=registry, model=model)
    return out


# --------------------------------------------------------------------------
# ChannelSpec JSON (manifest round-trip for serving handoff)
# --------------------------------------------------------------------------

def transport_to_json(transport):
    """Transport policy -> JSON-able form (inverse of
    :func:`transport_from_json`)."""
    if transport is None:
        return None
    if isinstance(transport, str):
        return transport
    if isinstance(transport, TransportConfig):
        return {"kind": transport.kind, "hop_chunks": transport.hop_chunks}
    raise TypeError(f"bad transport spec: {transport!r}")


def transport_from_json(d):
    if d is None or isinstance(d, str):
        return d
    return TransportConfig(kind=d["kind"],
                           hop_chunks=int(d.get("hop_chunks", 1)))


def spec_to_json(spec: ChannelSpec) -> Dict:
    """Placement/policy fields of a spec as JSON (the codec itself
    travels separately — registry JSON / container headers)."""
    out = {
        "transport": transport_to_json(spec.transport),
        "axis": spec.axis,
        "axis_size": spec.axis_size,
        "use_kernels": spec.use_kernels,
        "enabled": spec.enabled,
        "scale_dtype": spec.scale_dtype,
    }
    # Only emitted when bound, so flat-channel manifests keep their
    # pre-pod shape byte for byte.
    if spec.pod_axis is not None:
        out["pod_axis"] = spec.pod_axis
        out["pod_axis_size"] = spec.pod_axis_size
    return out


def spec_from_json(d: Dict, codec=None, cfg=None) -> ChannelSpec:
    return ChannelSpec(
        codec=codec, cfg=cfg,
        transport=transport_from_json(d.get("transport")),
        axis=d.get("axis"),
        axis_size=(None if d.get("axis_size") is None
                   else int(d["axis_size"])),
        pod_axis=d.get("pod_axis"),
        pod_axis_size=(None if d.get("pod_axis_size") is None
                       else int(d["pod_axis_size"])),
        use_kernels=d.get("use_kernels"),
        enabled=d.get("enabled"),
        scale_dtype=d.get("scale_dtype"),
    )
