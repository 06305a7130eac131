"""Mixture-of-Experts FFN: shared + routed experts, top-k routing with
capacity, scatter/gather dispatch.

Three dispatch implementations (``MoEConfig.impl``, validated against
:data:`SUPPORTED_IMPLS`):

  * ``"gspmd"``: experts stay sharded over the model axis; dispatch is a
    scatter/gather + batched einsum, GSPMD inserts the collectives.
  * ``"grouped_local"``: the same math vmapped over dp-aligned token
    groups so scatters stay shard-local (perf variant — see
    :func:`_moe_grouped`).
  * ``"shardmap_a2a"``: explicit expert-parallel dispatch under a fully
    manual ``shard_map`` — tokens cross the model axis through an
    ``all_to_all``, optionally as QLC-compressed containers (the
    paper's technique applied to MoE traffic). Routing and capacity
    drops are bit-identical to ``"gspmd"`` by construction: each rank
    reconstructs the global arrival-order positions from an integer
    counts all-gather (see :func:`_moe_shardmap_a2a`).

The compressed wire is opened by binding ``moe/dispatch`` /
``moe/combine`` channels (:data:`MOE_DISPATCH` / :data:`MOE_COMBINE`,
calibrated by ``repro.comm.calibrate.calibrate_moe_entries``) with
:func:`bind_moe_channels` around the step's trace. Without bound
channels the a2a runs uncompressed (``lax.all_to_all``), bit-identical
to ``"gspmd"``.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import layers
from repro.parallel import sharding as shd
from repro.parallel.sharding import logical_constraint

#: Registry / channel names of the expert-dispatch wire codecs.
MOE_DISPATCH = "moe/dispatch"
MOE_COMBINE = "moe/combine"

#: ``MoEConfig.impl`` values :func:`moe_block` accepts.
SUPPORTED_IMPLS = ("gspmd", "grouped_local", "shardmap_a2a")


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    s_in = 1.0 / d ** 0.5
    s_out = 1.0 / m.d_expert ** 0.5
    p = {
        "router": jax.random.normal(ks[0], (d, m.num_experts),
                                    jnp.float32) * s_in,
        "w_in": jax.random.normal(
            ks[1], (m.num_experts, d, m.d_expert), dtype) * s_in,
        "w_gate": jax.random.normal(
            ks[2], (m.num_experts, d, m.d_expert), dtype) * s_in,
        "w_out": jax.random.normal(
            ks[3], (m.num_experts, m.d_expert, d), dtype) * s_out,
    }
    if m.num_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d, m.num_shared_experts * m.d_expert, "swiglu", dtype)
    return p


def moe_param_specs(cfg: ModelConfig):
    specs = {
        "router": ("embed", "expert"),
        "w_in": ("expert", "embed", "mlp"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_out": ("expert", "mlp", "embed"),
    }
    if cfg.moe and cfg.moe.num_shared_experts:
        specs["shared"] = layers.mlp_param_specs("swiglu")
    return specs


# --------------------------------------------------------------------------
# Routing (ONE router einsum, shared by dispatch and the aux loss)
# --------------------------------------------------------------------------

def _router_logits(params, x_flat: jnp.ndarray) -> jnp.ndarray:
    """x_flat: [N, D] -> router logits [N, E] (f32)."""
    return jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                      params["router"])


def _route(params, x_flat: jnp.ndarray, m: MoEConfig):
    """x_flat: [N, D] -> (expert_idx [N,k], gates [N,k], probs [N,E]).

    ``probs`` is the full softmax over the SAME logits the top-k ran on
    — the aux load-balance loss consumes it without a second router
    einsum (jit dead-code-eliminates it when unused).
    """
    logits = _router_logits(params, x_flat)
    top, idx = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    return idx, gates, probs


def aux_load_balance_loss(probs: jnp.ndarray, idx: jnp.ndarray,
                          m: MoEConfig) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss from precomputed
    routing artifacts (``probs``/``idx`` as returned by :func:`_route`)
    — the router einsum is shared with dispatch, not recomputed."""
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32).sum(1)
    frac_tokens = onehot.mean(0)
    frac_probs = probs.astype(jnp.float32).mean(0)
    return m.num_experts * jnp.sum(frac_tokens * frac_probs)


# --------------------------------------------------------------------------
# Shared dispatch-plan / FFN helpers
# --------------------------------------------------------------------------

def _capacity(n_tokens: int, m: MoEConfig) -> int:
    """Static per-expert buffer capacity for ``n_tokens`` routed tokens."""
    return max(1, int(n_tokens * m.top_k * m.capacity_factor
                      // m.num_experts))


def _positions_in_expert(flat_e: jnp.ndarray, num_experts: int):
    """Arrival-order position of each assignment within its expert
    (pre-capacity). ``flat_e [A]`` -> ``pos [A]`` — assignment *a* is
    the ``pos[a]``-th arrival at expert ``flat_e[a]`` in sequence
    order. Every impl derives its capacity drops from this one
    primitive, which is what makes drops bit-identical across impls."""
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]


def _expert_ffn(buf: jnp.ndarray, w_in, w_gate, w_out) -> jnp.ndarray:
    """Row-wise swiglu expert FFN on a buffer ``[E, C, D]``. No biases,
    so all-zero rows (padding / other ranks' slots) map to exactly
    zero — the property the expert-parallel path relies on."""
    h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(buf.dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(buf.dtype))


# --------------------------------------------------------------------------
# Channel binding + traffic capture (trace-time context)
# --------------------------------------------------------------------------

_MOE_CTX = threading.local()


@contextlib.contextmanager
def bind_moe_channels(channels):
    """Bind the expert-dispatch wire channels for ``shardmap_a2a``.

    ``channels`` maps :data:`MOE_DISPATCH` / :data:`MOE_COMBINE` to
    :class:`~repro.comm.channel.Channel` objects bound to the
    ``"model"`` axis. Enter this around the code that TRACES the loss
    (the step builders in ``repro.training.train_step`` do it for you
    via their ``moe_channels`` argument) — the binding is consulted at
    trace time, inside the expert ``shard_map``.

    ``repro.adaptive.AdaptiveChannel`` wrappers (see
    :func:`adaptive_moe_channels`) work here unchanged — attribute
    forwarding resolves the deployed codec at trace time. Because the
    binding is baked into the traced step, a codec hot-swap only
    reaches the expert wire after the step is REBUILT
    (``TrainingAdapter`` does exactly that for the training loop).
    """
    old = getattr(_MOE_CTX, "channels", None)
    _MOE_CTX.channels = channels
    try:
        yield
    finally:
        _MOE_CTX.channels = old


def bound_moe_channels():
    """The currently bound ``{name: Channel}`` map, or ``None``."""
    return getattr(_MOE_CTX, "channels", None)


def adaptive_moe_channels(controller, channels):
    """Wrap a ``{name: Channel}`` expert-wire map for codec hot-swap.

    Each channel is registered with the
    :class:`repro.adaptive.AdaptiveController` under its registry name
    (:data:`MOE_DISPATCH` / :data:`MOE_COMBINE`), so a drift-triggered
    ``register_revision`` atomically rebinds the map in place; rebuild
    the traced step afterwards to put the new codec on the wire.
    """
    return {name: controller.wrap(ch, name=name)
            for name, ch in channels.items()}


@contextlib.contextmanager
def capture_moe_traffic(out_list: list):
    """Capture each MoE layer's eager-mode ``(params, x)`` at
    :func:`moe_block` entry into ``out_list`` — the calibration hook
    ``repro.comm.calibrate.calibrate_moe_entries`` uses to see actual
    routed-token traffic. Traced calls are not captured."""
    old = getattr(_MOE_CTX, "capture", None)
    _MOE_CTX.capture = out_list
    try:
        yield out_list
    finally:
        _MOE_CTX.capture = old


def dispatch_traffic(params, x: jnp.ndarray, cfg: ModelConfig):
    """The per-layer expert-wire traffic: ``(dispatch buffer [E, C, D],
    combine buffer [E, C, D])`` of one MoE layer on input ``x`` — the
    token values entering / leaving the expert ``all_to_all``.
    Impl-independent (the gspmd dispatch math); calibration input."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)
    idx, _gates, _probs = _route(params, x_flat, m)
    capacity = _capacity(n, m)
    flat_e = idx.reshape(-1)
    pos = _positions_in_expert(flat_e, m.num_experts)
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity
                     + jnp.minimum(pos, capacity - 1),
                     m.num_experts * capacity)
    tok_idx = jnp.repeat(jnp.arange(n), m.top_k)
    buf = jnp.zeros((m.num_experts * capacity, d), x.dtype)
    buf = buf.at[slot].set(x_flat[tok_idx], mode="drop")
    buf = buf.reshape(m.num_experts, capacity, d)
    out_e = _expert_ffn(buf, params["w_in"], params["w_gate"],
                        params["w_out"])
    return buf, out_e


# --------------------------------------------------------------------------
# Dispatch implementations
# --------------------------------------------------------------------------

def moe_block(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: [B, S, D] -> [B, S, D]. Capacity-bounded top-k dispatch."""
    impl = cfg.moe.impl
    if impl not in SUPPORTED_IMPLS:
        raise ValueError(
            f"unknown MoEConfig.impl {impl!r}; supported impls are "
            f"{SUPPORTED_IMPLS}")
    cap = getattr(_MOE_CTX, "capture", None)
    if cap is not None and not isinstance(x, jax.core.Tracer):
        cap.append((params, x))
    if impl == "grouped_local":
        return _moe_grouped(params, x, cfg)
    if impl == "shardmap_a2a":
        return _moe_shardmap_a2a(params, x, cfg)
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    x_flat = x.reshape(n, d)

    idx, gates, _probs = _route(params, x_flat, m)     # [N,k], [N,k]
    capacity = _capacity(n, m)

    # Position of each (token, k) assignment within its expert's buffer.
    flat_e = idx.reshape(-1)                          # [N*k]
    pos = _positions_in_expert(flat_e, m.num_experts)
    keep = pos < capacity
    slot = flat_e * capacity + jnp.minimum(pos, capacity - 1)  # [N*k]
    slot = jnp.where(keep, slot, m.num_experts * capacity)     # drop slot

    # Scatter tokens into expert buffers [E*C, D] (dropped -> discarded).
    tok_idx = jnp.repeat(jnp.arange(n), m.top_k)
    buf = jnp.zeros((m.num_experts * capacity, d), x.dtype)
    buf = buf.at[slot].set(x_flat[tok_idx], mode="drop")
    buf = buf.reshape(m.num_experts, capacity, d)
    buf = logical_constraint(buf, ("expert", None, "embed"))

    # Batched expert FFN (einsum over the expert dim; GSPMD shards it).
    out_e = _expert_ffn(buf, params["w_in"], params["w_gate"],
                        params["w_out"])
    out_e = out_e.reshape(m.num_experts * capacity, d)

    # Gather back and combine with gate weights.
    gathered = jnp.take(out_e, jnp.minimum(slot, out_e.shape[0] - 1), axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weighted = gathered * gates.reshape(-1)[:, None].astype(x.dtype)
    out = jnp.zeros((n, d), x.dtype).at[tok_idx].add(weighted)

    if m.num_shared_experts:
        out = out + layers.mlp(params["shared"], x, "swiglu").reshape(n, d)
    return out.reshape(b, s, d)


def _moe_grouped(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Grouped-local dispatch (perf variant, DESIGN.md / EXPERIMENTS §Perf).

    The global-buffer dispatch scatters batch-sharded tokens into an
    expert buffer whose sharding doesn't match — GSPMD lowers that to
    zeros + local scatter + ALL-REDUCE of the whole buffer (terabytes
    for mixtral train). Here tokens are split into ``dispatch_groups``
    groups aligned with the dp sharding; capacity is per (group,
    expert); scatters and gathers stay inside a group (= inside a
    shard), and the only cross-device traffic left is the inherent
    expert-TP all-reduce of the FFN outputs.
    """
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    g = min(m.dispatch_groups, n)
    while n % g:
        g -= 1
    ng = n // g
    x_flat = x.reshape(n, d)

    idx, gates, _probs = _route(params, x_flat, m)       # [N,k]
    capacity = _capacity(ng, m)
    xg = x_flat.reshape(g, ng, d)
    idx_g = idx.reshape(g, ng, m.top_k)
    gates_g = gates.reshape(g, ng, m.top_k).astype(x.dtype)
    tok_idx = jnp.repeat(jnp.arange(ng), m.top_k)

    def dispatch(xl, il):
        flat_e = il.reshape(-1)                           # [ng*k]
        pos = _positions_in_expert(flat_e, m.num_experts)
        keep = pos < capacity
        slot = flat_e * capacity + jnp.minimum(pos, capacity - 1)
        slot = jnp.where(keep, slot, m.num_experts * capacity)
        buf = jnp.zeros((m.num_experts * capacity, d), xl.dtype)
        buf = buf.at[slot].set(xl[tok_idx], mode="drop")
        return buf.reshape(m.num_experts, capacity, d), slot, keep

    bufs, slots, keeps = jax.vmap(dispatch)(xg, idx_g)
    bufs = logical_constraint(bufs, ("batch", "expert", None, "embed"))

    h = jnp.einsum("gecd,edf->gecf", bufs, params["w_in"].astype(x.dtype))
    gt = jnp.einsum("gecd,edf->gecf", bufs,
                    params["w_gate"].astype(x.dtype))
    h = jax.nn.silu(gt) * h
    h = logical_constraint(h, ("batch", "expert", None, "mlp"))
    out_e = jnp.einsum("gecf,efd->gecd", h,
                       params["w_out"].astype(x.dtype))
    out_e = out_e.reshape(g, m.num_experts * capacity, d)

    def combine(oe, slot, keep, gl):
        gathered = jnp.take(oe, jnp.minimum(slot, oe.shape[0] - 1), axis=0)
        gathered = jnp.where(keep[:, None], gathered, 0)
        weighted = gathered * gl.reshape(-1)[:, None]
        return jnp.zeros((ng, d), oe.dtype).at[tok_idx].add(weighted)

    out = jax.vmap(combine)(out_e, slots, keeps, gates_g).reshape(n, d)

    if m.num_shared_experts:
        out = out + layers.mlp(params["shared"], x, "swiglu").reshape(n, d)
    return out.reshape(b, s, d)


# --------------------------------------------------------------------------
# Expert-parallel shard_map all_to_all dispatch
# --------------------------------------------------------------------------

def shardmap_a2a_geometry(cfg: ModelConfig, n_tokens: int, mesh) -> dict:
    """Static per-rank a2a payload geometry of one MoE layer.

    Returns ``{"ng", "capacity", "c_send", "row_values", "axis_size"}``:
    each rank's all_to_all moves ``axis_size`` rows of ``row_values``
    f32 values (per direction, per layer) for ``ng`` local tokens.
    """
    m = cfg.moe
    dm = int(mesh.shape["model"])
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= int(mesh.shape[a])
    shards = dp * dm
    if n_tokens % shards:
        raise ValueError(
            f"shardmap_a2a needs the token count ({n_tokens}) divisible "
            f"by the token shards (dp*model = {shards})")
    if m.num_experts % dm:
        raise ValueError(
            f"shardmap_a2a needs num_experts ({m.num_experts}) divisible "
            f"by the model axis ({dm})")
    ng = n_tokens // shards
    capacity = _capacity(n_tokens, m)
    # top_k experts are distinct per token, so a rank sends at most
    # min(ng, capacity) rows to any one expert — the static send bound.
    c_send = min(ng, capacity)
    return {"ng": ng, "capacity": capacity, "c_send": c_send,
            "row_values": (m.num_experts // dm) * c_send * cfg.d_model,
            "axis_size": dm}


def _raw_a2a(axis: str):
    def a2a(v):
        return jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    return a2a


def _channel_a2a(ch, axis: str):
    """Compressed a2a as a straight-through ``custom_vjp``.

    Forward moves the activations as QLC containers
    (``Channel.all_to_all``); the QLC coding is lossless on the e4m3
    symbols, but the integer encode/decode has no gradient, so the
    backward pass routes the cotangent through the raw ``all_to_all``
    (its own transpose). Gradient-wire compression is the train step's
    separate reduce-scatter subsystem — activations-forward is where
    the expert bandwidth bound lives.
    """
    raw = _raw_a2a(axis)

    def wire(v):
        vals, _ok = ch.all_to_all(v)
        return vals.astype(v.dtype)

    f = jax.custom_vjp(wire)

    def fwd(v):
        return wire(v), None

    def bwd(_res, g):
        return (raw(g),)

    f.defvjp(fwd, bwd)
    return f


def _moe_shardmap_a2a(params, x: jnp.ndarray,
                      cfg: ModelConfig) -> jnp.ndarray:
    """Expert-parallel dispatch under a fully-manual ``shard_map``.

    Tokens are sharded contiguously over (pod?, data?, model) on their
    leading dim, experts over the model axis. Per rank:

    1. route the local ``ng`` tokens (replicated router — per-token,
       so identical to global routing);
    2. ``all_gather`` the per-expert assignment COUNTS (int32, never
       the values) in rank-major order and prefix-sum them — rank r's
       exclusive offset into each expert's global arrival order. Since
       global token order is rank-major, ``offset[e] + pos_local``
       IS gspmd's global cumsum position, so ``keep = pos_global <
       capacity`` reproduces its capacity drops bit for bit — and each
       rank's kept assignments are a PREFIX of its local arrival
       order, so send slots pack contiguously and the receiver
       reconstructs global positions from the counts alone (no index
       metadata on the value wire);
    3. ``all_to_all`` the packed ``[axis_size, E_local, C_send, D]``
       send buffer over the model axis — raw, or as QLC containers
       when :func:`bind_moe_channels` provided channels;
    4. scatter received rows at their reconstructed global positions
       (disjoint across sources — exact), run the local experts' FFN
       (zero rows stay zero: no biases), gather the same positions
       back and reverse the a2a;
    5. combine with gate weights on the local tokens.

    Only the model-axis a2a moves values; dp groups exchange nothing
    but the counts gather. The escape-pool ``ok`` flag is not surfaced:
    the empirically-calibrated plans size pools for the measured escape
    rate, and CI asserts value-identity of the compressed wire against
    its raw-e4m3 twin.
    """
    m = cfg.moe
    mesh = shd._current_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            "moe.impl='shardmap_a2a' needs a mesh with a 'model' axis in "
            "scope (repro.parallel.sharding.use_mesh)")
    b, s, d = x.shape
    n = b * s
    geo = shardmap_a2a_geometry(cfg, n, mesh)
    dm, ng, capacity, c_send = (geo["axis_size"], geo["ng"],
                                geo["capacity"], geo["c_send"])
    el = m.num_experts // dm                       # local experts
    token_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.axis_names) + ("model",)
    channels = bound_moe_channels()
    if channels is not None:
        dispatch_a2a = _channel_a2a(channels[MOE_DISPATCH], "model")
        combine_a2a = _channel_a2a(channels[MOE_COMBINE], "model")
    else:
        dispatch_a2a = combine_a2a = _raw_a2a("model")

    def body(xl, router, w_in, w_gate, w_out):
        # xl [ng, D] local token chunk; w_* [el, ...] local experts.
        idx, gates, _probs = _route({"router": router}, xl, m)
        flat_e = idx.reshape(-1)                   # [ng*k]
        pos_local = _positions_in_expert(flat_e, m.num_experts)
        counts = jax.nn.one_hot(flat_e, m.num_experts,
                                dtype=jnp.int32).sum(0)          # [E]

        # Rank-major counts gather: innermost token axis first, so
        # reshape(-1, E) indexes ranks in global token order.
        g = counts
        for ax in reversed(token_axes):
            g = jax.lax.all_gather(g, ax)
        g = g.reshape(-1, m.num_experts)           # [R, E]
        offsets = jnp.cumsum(g, axis=0) - g        # exclusive prefix

        r_me = jnp.int32(0)
        for ax in token_axes:
            r_me = r_me * mesh.shape[ax] + jax.lax.axis_index(ax)
        off_me = jax.lax.dynamic_index_in_dim(offsets, r_me, axis=0,
                                              keepdims=False)    # [E]

        # Bit-identical global capacity drops (gspmd's cumsum order).
        pos_global = off_me[flat_e] + pos_local
        keep = pos_global < capacity

        # Pack kept assignments: their local positions are a prefix per
        # expert, so pos_local IS the send slot.
        tok_idx = jnp.repeat(jnp.arange(ng), m.top_k)
        slot = flat_e * c_send + jnp.minimum(pos_local, c_send - 1)
        slot = jnp.where(keep, slot, m.num_experts * c_send)
        sbuf = jnp.zeros((m.num_experts * c_send, d), xl.dtype)
        sbuf = sbuf.at[slot].set(xl[tok_idx], mode="drop")
        sbuf = sbuf.reshape(dm, el, c_send, d)     # dest-major rows

        recv = dispatch_a2a(sbuf)                  # [dm, el, c_send, D]

        # Reconstruct each source's global positions for MY experts
        # from the counts gather (my model-group peers share my
        # (pod, data) coordinates: flat ranks [base, base + dm)).
        base = (r_me // dm) * dm
        my_model = jax.lax.axis_index("model")
        off_grp = jax.lax.dynamic_slice(
            offsets, (base, my_model * el), (dm, el))            # [dm, el]
        cnt_grp = jax.lax.dynamic_slice(
            g, (base, my_model * el), (dm, el))
        kept_grp = jnp.clip(capacity - off_grp, 0, cnt_grp)
        s_idx = jnp.arange(c_send)[None, None, :]
        valid = s_idx < kept_grp[:, :, None]       # [dm, el, c_send]
        e_idx = jnp.broadcast_to(jnp.arange(el)[None, :, None],
                                 valid.shape)
        rpos = jnp.where(valid,
                         e_idx * capacity + off_grp[:, :, None] + s_idx,
                         el * capacity)            # drop slot
        rbuf = jnp.zeros((el * capacity, d), xl.dtype)
        rbuf = rbuf.at[rpos.reshape(-1)].set(
            recv.reshape(-1, d).astype(xl.dtype), mode="drop")
        rbuf = rbuf.reshape(el, capacity, d)

        out_local = _expert_ffn(rbuf, w_in, w_gate, w_out)

        # Gather the same positions back and reverse the exchange.
        gathered = jnp.take(
            out_local.reshape(el * capacity, d),
            jnp.minimum(rpos.reshape(-1), el * capacity - 1), axis=0)
        gathered = jnp.where(valid.reshape(-1)[:, None], gathered, 0)
        back = combine_a2a(gathered.reshape(dm, el, c_send, d))
        back = back.reshape(m.num_experts * c_send, d)

        # Per-assignment combine on the local tokens (gspmd's gather).
        comb = jnp.take(back, jnp.minimum(slot, back.shape[0] - 1),
                        axis=0)
        comb = jnp.where(keep[:, None], comb, 0)
        weighted = comb * gates.reshape(-1)[:, None].astype(xl.dtype)
        return jnp.zeros((ng, d), xl.dtype).at[tok_idx].add(weighted)

    tok_spec = jax.sharding.PartitionSpec(token_axes)
    rep = jax.sharding.PartitionSpec()
    exp = jax.sharding.PartitionSpec("model")
    out = shd.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, rep, exp, exp, exp),
        out_specs=tok_spec,
    )(x.reshape(n, d), params["router"], params["w_in"],
      params["w_gate"], params["w_out"])

    if m.num_shared_experts:
        out = out + layers.mlp(params["shared"], x, "swiglu").reshape(n, d)
    return out.reshape(b, s, d)
