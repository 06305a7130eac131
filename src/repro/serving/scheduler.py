"""Continuous-batching serving engine over a shared compressed block pool.

This is the request-based serving API the ROADMAP's millions-of-users
north star needs: ``generate``-style per-call batches cannot express
requests that join and leave mid-flight, so the engine owns ONE padded
active set of ``max_batch`` slots and drives it step by step:

    Engine.submit(GenerationRequest) -> handle     (enqueue, no compute)
    Engine.step()                                  (admit + one batched
                                                    decode step + paging)
    Engine.poll(handle) -> RequestStatus           (tokens so far)

Scheduling model (all host-side, fully deterministic):

* **Admission** — waiting requests claim free slots in submit order,
  subject to a per-tenant fairness cap (``fairness_cap`` × max_batch
  concurrent slots per tenant) and, under a bounded
  :class:`~repro.comm.blockpool.BlockPool` with host spill disabled, a
  projected-bytes admission check that rejects with a typed
  ``PoolExhausted`` instead of OOMing mid-decode. Each admitted prompt
  prefills at batch 1 on fresh states and scatters into its slot row.
* **Decode** — ONE jitted ``decode_step`` over the whole padded slot
  set per engine step (free slots feed token 0 at position 0; every
  per-row op in the decode path is row-independent, so padding rows
  cannot perturb active rows — the engine's output is token-identical
  to running each request alone, asserted in tests).
* **Paging** — each slot pages its completed blocks through the shared
  :class:`~repro.serving.kv_cache.PagedKVCache` block codec into the
  global :class:`~repro.comm.blockpool.BlockPool`. Pool capacity is
  compressed bytes, so the codec's ratio is literally the number of
  extra concurrent sequences per device; identical prompt prefixes
  dedup by container digest (prefix sharing) and diverge copy-on-write
  (immutable blocks, new digests past the split point). Every decoded
  block is read back FROM the pooled container, so shared bytes are on
  the token hot path, not a shadow copy. Sync paging codes at most one
  block a slot per step: an admitted prompt's blocks page out over the
  steps that follow, so one long prompt does not stall every slot for
  the whole of its paging.

Profiler spans (``jax.profiler.TraceAnnotation``, a few per step and
per block, cheap while no trace records) mark where the host spends an
engine step, nested on the calling thread: ``engine.step`` holds
``engine.admit`` (holding ``engine.prefill``, ``kv.calibrate`` and
``engine.slot_write``), ``engine.decode`` and ``engine.page``; a sync
``engine.page`` holds, for each state slot ``l{i}``, ``kv.encode``,
``pool.put``, ``kv.decode``, ``kv.restore``, then one
``engine.slot_write``. ``rid`` ties the spans of one request together;
``engine.prefill`` carries the prefill's ``mode`` (``"bulk"`` or
``"serial"``, ``engine.prefill_mode``), and ``stats()``
``prefill_bulk_tokens`` counts the prompt tokens of bulk prefills.
``stats()["kv"]`` counts the blocks paged through the host path, the
wall seconds they took, and the dense and wire bytes of every pooled
block.

The legacy ``generate`` / ``generate_paged`` / ``generate_from_wire``
functions are deprecated wrappers building a one-engine run
(``repro.serving.engine``), asserted token-identical to the scan-based
oracle they replaced.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.comm.blockpool import (ArenaExhausted, BlockArena, BlockPool,
                                  PoolExhausted)
from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import init_decode_states, ssm
from repro.serving.engine import (_decode_window, _paged_step,
                                  _prefill_fn, _prefill_from_fn,
                                  prefill_mode)
from repro.serving.kv_cache import (KVCacheSpec, PagedKVCache,
                                    SSMBoundaryTracker, calibrate_cache)

_rid_counter = itertools.count()


@dataclasses.dataclass
class GenerationRequest:
    """One generation request: a prompt (1-D token array), a budget,
    and a tenant for fairness accounting."""
    prompt: Any
    max_new_tokens: int = 32
    tenant: str = "default"
    request_id: Optional[str] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        if self.request_id is None:
            self.request_id = f"req{next(_rid_counter)}"


@dataclasses.dataclass(frozen=True)
class RequestStatus:
    """Snapshot of a request's lifecycle (``Engine.poll``)."""
    request_id: str
    tenant: str
    state: str                  # waiting | running | finished | rejected
    tokens: np.ndarray          # generated tokens so far, int32 [<= budget]
    error: Optional[str] = None


@dataclasses.dataclass
class _Seq:
    """Engine-internal per-request state."""
    req: GenerationRequest
    state: str = "waiting"
    slot: Optional[int] = None
    toks: List[int] = dataclasses.field(default_factory=list)
    evicted: int = 0            # tokens behind this sequence's cold blocks
    digests: List[str] = dataclasses.field(default_factory=list)
    snap_digests: Dict[str, str] = dataclasses.field(default_factory=dict)
    error: Optional[str] = None

    @property
    def rid(self) -> str:
        return self.req.request_id

    @property
    def prompt_len(self) -> int:
        return int(self.req.prompt.size)

    @property
    def absorbed(self) -> int:
        """Tokens written into this sequence's cache so far (the last
        generated token has not been fed back yet)."""
        return self.prompt_len + max(0, len(self.toks) - 1)


def _slot_view(states, b: int):
    """Batch-row ``b`` of a decode-states pytree (every leaf is
    ``[n_groups, batch, ...]`` — batch is axis 1 throughout)."""
    return jax.tree.map(lambda a: a[:, b:b + 1], states)


def _slot_write(states, b: int, row):
    return jax.tree.map(lambda dst, src: dst.at[:, b:b + 1].set(src),
                        states, row)


class Engine:
    """Continuous-batching engine (see module docstring).

    ``kv_spec`` switches on compressed block paging: blocks go through
    the :class:`PagedKVCache` codec into ``pool`` (a
    :class:`~repro.comm.blockpool.BlockPool`; default: an effectively
    unbounded one). ``registry`` is calibrated lazily from the FIRST
    admitted request's prefill states when it lacks the
    ``kv/layer{i}`` entries. ``fairness_cap`` (0 < cap <= 1) bounds any
    one tenant to ``ceil(cap * max_batch)`` concurrent slots.

    ``kv_paging="async"`` (requires ``KVCacheSpec(mode="qlc",
    exact_capacity=False)``) moves paging device-resident: evicted
    block containers live in a :class:`~repro.comm.blockpool.BlockArena`
    of ``arena_slots`` slots, block decodes are DMA-prefetched at
    window boundaries, and decode runs a whole admission window with
    the greedy feedback on device (constant host transfers per
    window). Token
    output is identical to ``"sync"``; both paging modes share one
    pool (device-framed containers are byte-identical to host ones).
    """

    def __init__(self, params, cfg: ModelConfig, *, max_seq_len: int,
                 max_batch: int = 4, kv_spec: Optional[KVCacheSpec] = None,
                 registry=None, pool: Optional[BlockPool] = None,
                 fairness_cap: Optional[float] = None, mesh=None,
                 kv_paging: str = "sync", arena_slots: int = 256):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if kv_paging not in ("sync", "async"):
            raise ValueError(f"kv_paging must be 'sync' or 'async', got "
                             f"{kv_paging!r}")
        if kv_paging == "async":
            if kv_spec is None or kv_spec.mode != "qlc" \
                    or kv_spec.exact_capacity:
                raise ValueError(
                    "kv_paging='async' needs KVCacheSpec(mode='qlc', "
                    "exact_capacity=False): the fixed plan geometry is "
                    "what makes block containers compile-time-constant "
                    "frames the device encode/decode can share")
        self.params = params
        self.cfg = cfg
        self.max_seq_len = int(max_seq_len)
        self.max_batch = int(max_batch)
        self.kv_spec = kv_spec
        if kv_spec is not None and registry is None:
            from repro.core.registry import CodecRegistry
            registry = CodecRegistry()
        self.registry = registry
        if kv_spec is not None and pool is None:
            pool = BlockPool(1 << 50)       # effectively unbounded
        self.pool = pool
        self._mesh = mesh
        self._codec: Optional[PagedKVCache] = None
        self._kinds = cfg.layer_kinds()
        self._tenant_cap = (None if fairness_cap is None
                            else max(1, math.ceil(fairness_cap * max_batch)))
        self._seqs: Dict[str, _Seq] = {}
        self._waiting: List[str] = []
        self._slots: List[Optional[str]] = [None] * self.max_batch
        self._states = init_decode_states(cfg, self.max_batch,
                                          self.max_seq_len)
        self._step_fn = _paged_step(cfg)
        self._prefill = _prefill_fn(cfg)
        self._prefill_from = _prefill_from_fn(cfg)
        self._prefill_mode = prefill_mode(cfg)
        self.kv_paging = kv_paging
        self._arena_slots = int(arena_slots)
        #: boundary-state snapshots for SSM re-basing (qlc only)
        self._snaps = SSMBoundaryTracker()
        self._rebase = (kv_spec is not None and kv_spec.ssm_rebase
                        and any(k != "attention" for k in self._kinds))
        #: prefetch handles scheduled at the last block boundary,
        #: consumed after the NEXT window's dispatch: (rid, handle)
        self._pending: List[tuple] = []
        self._windows = 0
        self._window_h2d = 0        # host->device uploads per async run
        self._window_d2h = 0        # device->host reads per async run
        #: deterministic scheduling trace: (step, event, request_id)
        self.events: List[tuple] = []
        self._step_idx = 0
        self._prefill_s = 0.0
        self._prefill_tokens = 0
        self._prefill_bulk_tokens = 0   # of them, in one forward pass
        self._decode_s = 0.0
        self._decode_tokens = 0
        self._blocks_paged = 0      # blocks through _evict_slot
        self._page_s = 0.0          # wall seconds inside _evict_slot
        self._pooled_dense_bytes = 0    # summed over every _pool_put
        self._pooled_wire_bytes = 0
        self._dense_of: Dict[str, int] = {}     # digest -> dense bytes
        self._dense_logical = 0
        self.peak_dense_logical_bytes = 0

    # ---- request lifecycle ----------------------------------------------

    def submit(self, req: GenerationRequest) -> str:
        """Enqueue a request; returns its handle (no compute happens
        until :meth:`step`)."""
        rid = req.request_id
        if rid in self._seqs:
            raise ValueError(f"duplicate request_id {rid!r}")
        if req.prompt.size + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"request {rid!r} needs {req.prompt.size} prompt + "
                f"{req.max_new_tokens} new tokens > max_seq_len="
                f"{self.max_seq_len}")
        self._seqs[rid] = _Seq(req=req)
        self._waiting.append(rid)
        self._log("submit", rid)
        return rid

    def poll(self, handle: str) -> RequestStatus:
        seq = self._seqs[handle]
        return RequestStatus(request_id=seq.rid, tenant=seq.req.tenant,
                             state=seq.state,
                             tokens=np.asarray(seq.toks, np.int32),
                             error=seq.error)

    def step(self) -> int:
        """Admit what fits, run ONE batched decode step over the padded
        active set (one admission *window* of steps under
        ``kv_paging="async"``), page completed blocks. Returns the
        number of requests still in flight (waiting + running)."""
        self._step_idx += 1
        with TraceAnnotation("engine.step", step=self._step_idx):
            if self.kv_paging == "async":
                self._step_async()
            else:
                self._step_sync()
        return sum(1 for s in self._seqs.values()
                   if s.state in ("waiting", "running"))

    def _step_sync(self):
        self._admit()
        active = [(b, rid) for b, rid in enumerate(self._slots)
                  if rid is not None]
        if active:
            tokens = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch, 1), np.int32)
            for b, rid in active:
                seq = self._seqs[rid]
                tokens[b, 0] = seq.toks[-1]
                pos[b, 0] = seq.prompt_len + len(seq.toks) - 1
            t0 = time.perf_counter()
            with TraceAnnotation("engine.decode", active=len(active)):
                nxt, _, self._states = self._step_fn(
                    self.params, jnp.asarray(tokens), self._states,
                    jnp.asarray(pos))
                nxt = np.asarray(nxt)       # forces the dispatch
            self._decode_s += time.perf_counter() - t0
            self._decode_tokens += len(active)
            for b, rid in active:
                seq = self._seqs[rid]
                seq.toks.append(int(nxt[b, 0]))
                self._note_boundary(seq)
                try:
                    self._page(seq, most=1)
                except PoolExhausted as e:
                    self._reject(seq, e)
                    continue
                if len(seq.toks) >= seq.req.max_new_tokens:
                    self._finish(seq)

    def _step_async(self):
        """One *admission window* of decode steps
        (``engine._decode_window``): the host uploads one seed token +
        position per slot, the greedy feedback stays on device, and
        one array of generated tokens comes back — host transfers per
        window are constant (2 up, 1 down), independent of the window
        length. The window ends exactly at the nearest block boundary
        or budget across active slots, so evictions (and SSM boundary
        snapshots) only ever happen between windows; the prefetch
        decodes scheduled there are consumed after the NEXT window's
        result lands, which is what hides them behind model compute."""
        self._admit()
        active = [(b, rid) for b, rid in enumerate(self._slots)
                  if rid is not None]
        if active:
            bt = self.kv_spec.block_tokens
            hot = self.kv_spec.hot_blocks
            window = None
            for _, rid in active:
                seq = self._seqs[rid]
                to_finish = seq.req.max_new_tokens - len(seq.toks)
                to_boundary = (seq.evicted + (1 + hot) * bt
                               - seq.absorbed)
                w = min(to_finish, to_boundary)
                if self._rebase:
                    # also stop at recording boundaries (multiples of
                    # bt) so SSM boundary snapshots are never skipped
                    w = min(w, bt - seq.absorbed % bt)
                window = w if window is None else min(window, w)
            window = max(1, window)
            tokens = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch, 1), np.int32)
            for b, rid in active:
                seq = self._seqs[rid]
                tokens[b, 0] = seq.toks[-1]
                pos[b, 0] = seq.prompt_len + len(seq.toks) - 1
            t0 = time.perf_counter()
            with TraceAnnotation("engine.decode", active=len(active)):
                tok_dev = jnp.asarray(tokens)
                pos_dev = jnp.asarray(pos)
                self._window_h2d += 2
                with jax.transfer_guard("disallow"):
                    # The probe: any per-token host transfer inside the
                    # window would raise here.
                    gen_dev, self._states = _decode_window(
                        self.cfg, self.params, tok_dev, pos_dev,
                        self._states, window)
                gen = np.asarray(gen_dev)   # ONE d2h for the window
                self._window_d2h += 1
                self._windows += 1
                # Last boundary's prefetch decodes ran behind this
                # window on the in-order device stream — wait on them
                # now (timed: a stall here is the cost prefetch failed
                # to hide) ...
                ready = self._consume_pending()
            self._decode_s += time.perf_counter() - t0
            self._decode_tokens += len(active) * window
            # ... and apply them untimed, like the sync path's _page.
            self._apply_pending(ready)
            for b, rid in active:
                seq = self._seqs[rid]
                if seq.state != "running":      # rejected at consume
                    continue
                seq.toks.extend(int(t) for t in gen[b, :window])
                self._note_boundary(seq)
                try:
                    self._page(seq)
                except PoolExhausted as e:
                    self._reject(seq, e)
                    continue
                if len(seq.toks) >= seq.req.max_new_tokens:
                    self._finish(seq)

    def run(self):
        """Drive :meth:`step` until every submitted request finished or
        was rejected."""
        while self.step():
            pass

    # ---- admission -------------------------------------------------------

    def _admit(self):
        for rid in list(self._waiting):
            if None not in self._slots:
                break
            seq = self._seqs[rid]
            tenant = seq.req.tenant
            if self._tenant_cap is not None and \
                    self._tenant_active(tenant) >= self._tenant_cap:
                self._log("defer_fairness", rid)
                continue
            if self.pool is not None and self.kv_spec is not None:
                try:
                    self.pool.check_admission(self._projected_bytes(seq))
                except PoolExhausted as e:
                    self._waiting.remove(rid)
                    self._reject(seq, e, event="reject_admission")
                    continue
            self._waiting.remove(rid)
            try:
                self._start(seq)
            except PoolExhausted as e:
                self._reject(seq, e)

    def _tenant_active(self, tenant: str) -> int:
        return sum(1 for rid in self._slots if rid is not None
                   and self._seqs[rid].req.tenant == tenant)

    def _projected_bytes(self, seq: _Seq) -> float:
        """Projected compressed footprint of a request, in the pool's
        measured mean-block-bytes unit (0 before any block pooled —
        the first request always gets to run and establish the unit)."""
        if self.kv_spec is None or self.pool is None:
            return 0.0
        mean = self.pool.mean_block_bytes()
        if not mean:
            return 0.0
        bt = self.kv_spec.block_tokens
        total = seq.prompt_len + seq.req.max_new_tokens - 1
        n_blocks = max(0, total // bt - self.kv_spec.hot_blocks)
        return mean * n_blocks * len(self._kinds)

    def _start(self, seq: _Seq):
        with TraceAnnotation("engine.admit", rid=seq.rid,
                             prompt_len=seq.prompt_len):
            b = self._slots.index(None)
            t0 = time.perf_counter()
            with TraceAnnotation("engine.prefill", rid=seq.rid,
                                 tokens=seq.prompt_len,
                                 mode=self._prefill_mode):
                first, row = self._prefill_row(seq)
            self._prefill_s += time.perf_counter() - t0
            self._prefill_tokens += seq.prompt_len
            if self._prefill_mode == "bulk":
                self._prefill_bulk_tokens += seq.prompt_len
            if self.kv_spec is not None and self._codec is None:
                self._ensure_codec(row, seq.prompt_len)
            self._write_slot(seq, b, row)
            self._slots[b] = seq.rid
            seq.slot = b
            seq.state = "running"
            seq.toks = [first]
            self._log("admit", seq.rid)
            if self.kv_paging == "async":
                # windows end at block boundaries: page the prompt now
                self._page(seq)
            if len(seq.toks) >= seq.req.max_new_tokens:
                self._finish(seq)

    def _prefill_row(self, seq: _Seq):
        """Prefill ``seq``'s prompt into a fresh batch-1 row; returns
        its first token (read back, so the prefill has finished) and
        the row."""
        row = init_decode_states(self.cfg, 1, self.max_seq_len)
        if self._rebase:
            # Segmented prefill: pause at every block boundary to
            # capture the recurrent layers' boundary states (the
            # re-basing snapshots). The same computation as one
            # whole-prompt prefill — same mode, same positions.
            bt = self.kv_spec.block_tokens
            prompt = seq.req.prompt
            logits, pos = None, 0
            while pos < seq.prompt_len:
                end = min(seq.prompt_len, (pos // bt + 1) * bt)
                seg = jnp.asarray(prompt[None, pos:end])
                logits, row = self._prefill_from(
                    self.params, seg, row, jnp.int32(pos))
                pos = end
                if pos % bt == 0:
                    self._record_boundary_states(seq, row, pos)
        else:
            prompts = jnp.asarray(seq.req.prompt[None, :])
            logits, row = self._prefill(self.params, prompts, row)
        return int(np.argmax(np.asarray(logits)[0])), row

    def _write_slot(self, seq: _Seq, b: int, row):
        """Scatter a batch-1 row into slot ``b`` of the decode states."""
        with TraceAnnotation("engine.slot_write", rid=seq.rid):
            self._states = _slot_write(self._states, b, row)

    def _ensure_codec(self, row_states, tokens: int):
        """Build the shared block codec, calibrating the registry's
        ``kv/layer{i}`` entries from the first prefill when absent."""
        with TraceAnnotation("kv.calibrate"):
            base = self.kv_spec.layer_codec(0)
            have = any(n == base or n.startswith(base + "/")
                       for n in self.registry.names())
            if not have:
                calibrate_cache(self.registry, self.cfg, row_states,
                                tokens, self.kv_spec)
            self._codec = PagedKVCache(self.kv_spec, self.cfg,
                                       self.registry, mesh=self._mesh)

    # ---- paging through the shared pool ---------------------------------

    def _page(self, seq: _Seq, most: Optional[int] = None):
        """Page out ``seq``'s completed blocks, oldest first: all of
        them, or ``most`` at most. Sync decode pages one a step, so a
        long prompt's blocks page out over the steps after its
        admission instead of stalling every slot in one (a decode step
        completes at most one block a slot); :meth:`_finish` pages
        what is left."""
        if self._codec is None:
            return
        bt = self.kv_spec.block_tokens
        hot = self.kv_spec.hot_blocks
        evict = (self._evict_slot_async if self.kv_paging == "async"
                 else self._evict_slot)
        paged = 0
        while seq.evicted + (1 + hot) * bt <= seq.absorbed and paged != most:
            t0 = seq.evicted
            evict(seq, t0, t0 + bt)
            seq.evicted = t0 + bt
            paged += 1

    def _record_boundary_states(self, seq: _Seq, row, t: int):
        """Snapshot every recurrent layer's state at boundary ``t``
        (the state after absorbing exactly ``t`` tokens) for later
        re-based eviction."""
        snap = {f"l{i}": tuple(ssm.state_snapshot(row[f"l{i}"]))
                for i, kind in enumerate(self._kinds)
                if kind != "attention"}
        if snap:
            self._snaps.record(seq.rid, t, snap)

    def _note_boundary(self, seq: _Seq):
        """Capture boundary states the moment a running slot's absorbed
        count lands on a block boundary (no-op unless re-basing)."""
        if not self._rebase or seq.slot is None:
            return
        if seq.absorbed > 0 and seq.absorbed % self.kv_spec.block_tokens == 0:
            self._record_boundary_states(
                seq, _slot_view(self._states, seq.slot), seq.absorbed)

    def _evict_slot(self, seq: _Seq, t0: int, t1: int):
        """Encode one completed block of ``seq``'s slot row into the
        pool, then restore the row from the POOLED container — shared
        (deduped) bytes are what the model attends over."""
        tick = time.perf_counter()
        with TraceAnnotation("engine.page", rid=seq.rid, start=t0):
            row = _slot_view(self._states, seq.slot)
            new_row = dict(row)
            bsnap = (self._snaps.take(seq.rid, t1) if self._rebase
                     else None)
            for i, kind in enumerate(self._kinds):
                key = f"l{i}"
                st = row[key]
                if kind == "attention":
                    _, (k2, v2) = self._code_block(
                        seq, i, attn.kv_block_slice(st, t0, t1),
                        start=t0, tokens=t1 - t0)
                    with TraceAnnotation("kv.restore", layer=key):
                        new_row[key] = attn.kv_block_restore(
                            st, t0, t1, jnp.asarray(k2), jnp.asarray(v2))
                    continue
                if bsnap is not None and key in bsnap:
                    # Re-based snapshot: the state AT boundary t1 —
                    # depends only on tokens < t1, so shared prompt
                    # prefixes pool to identical digests. The live state
                    # (which has absorbed tokens past t1) is left
                    # untouched; the decode still runs so an overflowing
                    # container surfaces here, not on a later reader.
                    digest, _ = self._code_block(
                        seq, i, bsnap[key], start=t1, tokens=t1 - t0)
                else:
                    digest, decoded = self._code_block(
                        seq, i, ssm.state_snapshot(st), start=t1,
                        tokens=t1 - t0)
                    with TraceAnnotation("kv.restore", layer=key):
                        new_row[key] = ssm.state_restore(
                            st, [jnp.asarray(a) for a in decoded])
                # the newest snapshot supersedes the previous one
                old = seq.snap_digests.get(key)
                if old is not None:
                    self._pool_release(seq, old)
                seq.snap_digests[key] = digest
            self._write_slot(seq, seq.slot, new_row)
        self._blocks_paged += 1
        self._page_s += time.perf_counter() - tick

    def _code_block(self, seq: _Seq, i: int, arrays, *, start: int,
                    tokens: int):
        """Encode layer ``i``'s block, pool it, and decode it back FROM
        the pooled container; returns ``(digest, decoded arrays)``."""
        key = f"l{i}"
        with TraceAnnotation("kv.encode", layer=key):
            block = self._codec.encode_block_arrays(
                self.kv_spec.layer_codec(i), key, arrays, start=start,
                tokens=tokens)
        digest = self._pool_put(seq, block)
        with TraceAnnotation("kv.decode", layer=key):
            decoded = self._codec.decode_block_arrays(self.pool.get(digest))
        return digest, decoded

    # ---- async paging (device-resident arena + prefetch) -----------------

    def _ensure_arena(self, slot_words: int) -> BlockArena:
        if self._codec.arena is None:
            # The arena never holds more bytes than the pool may
            # reference: a slot frames a whole group-stacked block (all
            # layers of a phi3-mini block are ~40 MB), so 256 of them
            # would outgrow HBM. Blocks past the last slot decode
            # straight from their own device words.
            n_slots = self._arena_slots
            if self.pool is not None:
                n_slots = min(n_slots, max(
                    1, self.pool.capacity_bytes // (4 * slot_words)))
            arena = BlockArena(n_slots, slot_words)
            self._codec.arena = arena
            if self.pool is not None and self.pool.arena is None:
                self.pool.arena = arena
        return self._codec.arena

    def _evict_slot_async(self, seq: _Seq, t0: int, t1: int):
        """Async twin of :meth:`_evict_slot`: frame every layer's block
        on device, park the words in the arena, and SCHEDULE the
        prefetch decode — consumed after the next window lands
        (:meth:`_consume_pending`), so the decode runs behind model
        compute instead of on the block-boundary critical path. Escape
        overflow under the plan capacity falls back to the sync host
        path for the whole boundary (counted as a prefetch miss)."""
        with TraceAnnotation("engine.page", rid=seq.rid, start=t0):
            row = _slot_view(self._states, seq.slot)
            bsnap = (self._snaps.take(seq.rid, t1) if self._rebase else None)
            devs = []
            for i, kind in enumerate(self._kinds):
                key = f"l{i}"
                name = self.kv_spec.layer_codec(i)
                st = row[key]
                if kind == "attention":
                    arrays = attn.kv_block_slice(st, t0, t1)
                    start = t0
                elif bsnap is not None and key in bsnap:
                    arrays = bsnap[key]
                    start = t1
                else:
                    arrays = ssm.state_snapshot(st)
                    start = t1
                dev = self._codec.encode_block_device(
                    name, key, arrays, start=start, tokens=t1 - t0)
                if dev is None:
                    # plan-capacity escape overflow: redo this boundary on
                    # the host sync path (re-wires the section raw there)
                    self._codec.prefetcher.miss()
                    if bsnap is not None:
                        self._snaps.record(seq.rid, t1, bsnap)  # un-take
                    self._evict_slot(seq, t0, t1)
                    return
                devs.append(dev)
            arena = self._ensure_arena(max(d.plan.total_words for d in devs))
            for dev in devs:
                try:
                    slot, gen = arena.alloc()
                    arena.write(slot, dev.words)
                    dev.slot, dev.gen = slot, gen
                except ArenaExhausted:
                    dev.slot = None     # decode straight from the HBM words
                self._pending.append(
                    (seq.rid, self._codec.prefetcher.schedule(dev)))

    def _consume_pending(self):
        """Wait on the prefetch decodes scheduled at the last boundary:
        arena staleness check, then block until the decoded arrays are
        ready (a no-op when the prefetch overlapped — the stall time is
        what ``BlockPrefetcher`` meters). This is the only paging cost
        on the decode critical path, so it runs INSIDE the timed decode
        region; the restore + pool accounting (:meth:`_apply_pending`)
        is bookkeeping the sync path also does untimed in ``_page``."""
        pending, self._pending = self._pending, []
        ready = []
        for rid, handle in pending:
            seq = self._seqs[rid]
            if seq.state != "running":
                continue            # rejected/finished since scheduled
            ready.append((seq, handle,
                          self._codec.prefetcher.consume(handle)))
        return ready

    def _apply_pending(self, ready):
        """Apply consumed prefetches: attention-window restore from the
        decoded (pooled) bytes plus deferred pool/digest accounting.
        Deferring the attention restore by one window is exact: the
        ``"qlc"`` round trip is bit-identical, and the window never
        touches cache rows behind the eviction horizon."""
        for seq, handle, arrays in ready:
            if seq.state != "running":
                continue
            try:
                self._apply_consumed(seq, handle, arrays)
            except PoolExhausted as e:
                self._reject(seq, e)

    def _apply_consumed(self, seq: _Seq, handle, arrays):
        dev = handle.block
        block = dev.host_block()    # D2H started at schedule time
        digest = self._pool_put(seq, block)
        if dev.slot is not None:
            if not self.pool.attach_arena_slot(digest, dev.slot, dev.gen):
                # dedup hit: the pooled entry already owns an arena
                # copy of these bytes — recycle ours
                self._codec.arena.free(dev.slot)
        i = int(dev.layer[1:])
        if self._kinds[i] == "attention":
            full = dict(_slot_view(self._states, seq.slot))
            k2, v2 = arrays
            full[dev.layer] = attn.kv_block_restore(
                full[dev.layer], dev.start, dev.start + dev.tokens,
                k2, v2)
            self._write_slot(seq, seq.slot, full)
        else:
            # SSM: never restore — the live state has advanced past the
            # snapshot boundary. Supersede the previous snapshot.
            old = seq.snap_digests.get(dev.layer)
            if old is not None:
                self._pool_release(seq, old)
            seq.snap_digests[dev.layer] = digest

    def _flush_pending(self, seq: _Seq):
        """Consume (or drop, if no longer running) every pending
        prefetch of ``seq`` right now — called before finish/reject so
        deferred pool accounting can't outlive the request."""
        keep = []
        for rid, handle in self._pending:
            if rid != seq.rid:
                keep.append((rid, handle))
                continue
            if seq.state == "running":
                arrays = self._codec.prefetcher.consume(handle)
                self._apply_consumed(seq, handle, arrays)
        self._pending = keep

    def _pool_put(self, seq: _Seq, block) -> str:
        with TraceAnnotation("pool.put", layer=block.layer):
            digest = self.pool.put(block)
        seq.digests.append(digest)
        self._pooled_dense_bytes += block.dense_bytes
        self._pooled_wire_bytes += block.wire_bytes
        self._dense_of[digest] = block.dense_bytes
        self._dense_logical += block.dense_bytes
        self.peak_dense_logical_bytes = max(self.peak_dense_logical_bytes,
                                            self._dense_logical)
        return digest

    def _pool_release(self, seq: _Seq, digest: str):
        self.pool.release(digest)
        seq.digests.remove(digest)
        self._dense_logical -= self._dense_of.get(digest, 0)

    def _release_all(self, seq: _Seq):
        for digest in list(seq.digests):
            self._pool_release(seq, digest)
        seq.snap_digests.clear()

    # ---- completion / rejection -----------------------------------------

    def _finish(self, seq: _Seq):
        try:
            self._page(seq)
            if self._pending:
                self._flush_pending(seq)
        except PoolExhausted as e:
            self._reject(seq, e)
            return
        seq.state = "finished"
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        if self.pool is not None:
            self._release_all(seq)      # zero-ref blocks stay cached
        self._snaps.drop(seq.rid)
        self._log("finish", seq.rid)

    def _reject(self, seq: _Seq, err: Exception, event: str = "reject"):
        seq.state = "rejected"
        seq.error = f"{type(err).__name__}: {err}"
        if self._pending:
            self._flush_pending(seq)    # drops (state != running)
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        if self.pool is not None:
            self._release_all(seq)
        self._snaps.drop(seq.rid)
        self._log(event, seq.rid)

    def _log(self, event: str, rid: str):
        self.events.append((self._step_idx, event, rid))

    # ---- accounting ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Engine accounting: request states, ms/token prefill + decode
        (the speed.md reporting format), KV codec counters, and the
        pool's byte-level stats (with ``dense_logical`` rows so the
        capacity win — dense bytes a dense cache would pin vs pooled
        compressed bytes — is one division away).

        ``kv`` (paged engines, once the codec exists) is cumulative:
        ``blocks_paged`` and ``page_s`` count the blocks paged through
        the host path (:meth:`_evict_slot`) and their wall seconds;
        ``dense_bytes`` and ``wire_bytes`` sum every block put into the
        pool, dedup hits included."""
        by_state: Dict[str, int] = {}
        for s in self._seqs.values():
            by_state[s.state] = by_state.get(s.state, 0) + 1
        out: Dict[str, Any] = {
            "steps": self._step_idx,
            "requests": {st: by_state.get(st, 0) for st in
                         ("waiting", "running", "finished", "rejected")},
            "prefill_tokens": self._prefill_tokens,
            "prefill_bulk_tokens": self._prefill_bulk_tokens,
            "decode_tokens": self._decode_tokens,
            "ms_per_token_prefill": (1e3 * self._prefill_s
                                     / max(1, self._prefill_tokens)),
            "ms_per_token_decode": (1e3 * self._decode_s
                                    / max(1, self._decode_tokens)),
            "dense_logical_bytes": self._dense_logical,
            "peak_dense_logical_bytes": self.peak_dense_logical_bytes,
        }
        if self._codec is not None:
            out["kv"] = {
                "overflow_sections": self._codec.overflow_sections,
                "raw_sections": self._codec.raw_sections,
                "blocks_paged": self._blocks_paged,
                "page_s": self._page_s,
                "dense_bytes": self._pooled_dense_bytes,
                "wire_bytes": self._pooled_wire_bytes,
            }
        if self.kv_paging == "async":
            out["async"] = {
                "windows": self._windows,
                "window_h2d": self._window_h2d,
                "window_d2h": self._window_d2h,
                "h2d_per_window": (self._window_h2d
                                   / max(1, self._windows)),
                "d2h_per_window": (self._window_d2h
                                   / max(1, self._windows)),
            }
            if self._codec is not None:
                out["prefetch"] = self._codec.prefetcher.stats()
                if self._codec.arena is not None:
                    out["arena"] = self._codec.arena.stats()
        if self.pool is not None:
            out["pool"] = self.pool.stats()
        return out
