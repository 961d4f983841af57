"""Serving engine: continuous batching over the mixed-precision model API.

Public surface (the redesigned serving API):

* :class:`~repro.serving.config.EngineConfig` — one validated dataclass
  holding every knob (model, policy, cache backend, capacity); invalid
  combinations raise :class:`~repro.serving.config.EngineError` before any
  device memory is touched.
* ``submit(prompt, params) -> rid`` — enqueue a request; typed rejection
  (``EngineError``) for over-long prompts and pool-infeasible requests.
* ``step() -> List[RequestOutput]`` — one engine iteration; every running
  request yields an immutable :class:`~repro.serving.request.RequestOutput`
  snapshot (delta tokens, cumulative output, finish reason) instead of
  having its ``Request`` mutated behind the caller's back.
* ``generate(prompts, params)`` / ``stream(prompt, params)`` — batch and
  incremental conveniences built on ``step()``.
* ``abort(rid)`` — cancel a waiting or running request; a running paged
  request's KV blocks are reclaimed immediately.

The engine owns one batched quantized KV store (B = n_slots) in one of two
backends:

* ``cache_kind="dense"`` — the reference path: one ``(n_slots, max_seq)``
  slab per precision format (core/kvcache.py).
* ``cache_kind="paged"`` — block-pooled storage (core/paged_kvcache.py):
  a shared pool of ``block_size``-token blocks, a per-slot block table,
  and a host-side :class:`BlockAllocator`.  Admission is gated on free
  blocks (the scheduler's ``admit_gate``) and a request's blocks are
  reclaimed when it retires, so resident KV memory scales with *live
  context*, not ``n_slots × max_seq``.  By default admission *reserves*
  the worst case (``prompt + max_new_tokens`` blocks) so a running
  request can never stall; with ``enable_block_growth`` it reserves
  only the prompt's blocks (+ ``reserve_headroom_blocks``), ``step()``
  allocates one block lazily whenever a slot's next append crosses a
  block boundary, and pool exhaustion preempts the youngest running
  request — its blocks are freed, it requeues at the *front* of the
  waiting queue (``Status.PREEMPTED``), and on re-admission its whole
  stream — prompt *and* already-produced tokens — is re-fed in forced
  multi-token chunks through the ordinary step (fed from the recorded
  stream instead of the sampler, nothing re-emitted), so recovery is
  byte-exact in O(stream / prefill_chunk) iterations (DESIGN.md §5.3).
  With
  ``enable_prefix_caching``, full prompt blocks are additionally
  published in a content-addressed :class:`PrefixIndex`; a new request
  whose prompt matches a cached chain maps the *same physical blocks*
  into its table (refcounted, copy-on-write at the append frontier) —
  skipping their prefill compute and allocation entirely — and reports
  the hit as ``RequestOutput.cached_tokens`` (DESIGN.md §5.2).

Prompt ingestion is **pool-direct chunked prefill** for every KV-cache
family: prompt + produced output form one logical token stream per
request, ``step()`` feeds the next ``prefill_chunk`` unfed tokens of
every running request through one batched multi-token ``decode_step``,
and the chunk's KV is quantized and written *straight into the batch
store* (pool blocks / dense slab) — there is no staging cache, no
splice, and no separate prefill graph.  Prefill chunks, preemption
replay, and steady-state decode are all the same mixed step: a slot
mid-prompt contributes ``prefill_chunk`` rows, a decoding slot
contributes one valid row (the rest padding, dropped by the ragged
``valid`` mask), and both run the *same* per-block flash-decode update
(kernels/kvattn.flash_block_update) over bit-identical KV tiles — dense
walks the slab, paged resolves its block table inside the multi-query
kernel (kernels/paged_kvattn.py, no dense gather) with the grid bounded
by the batch's live context.  The two backends therefore produce
**bit-identical greedy streams** (locked down by
tests/test_engine_paged.py), and the stream is invariant to the chunk
partition (tests/test_kernels_mq_paged_attn.py).  Recurrent-state and
modality-stub families (no KV cache to page / extra encoder inputs) use
an exact-length one-shot prefill instead and decode one token per step.

Sampling is per-slot end-to-end: each request carries its own RNG stream
(``fold_in(PRNGKey(request seed), decode step)``), so seeded requests are
reproducible regardless of batch composition.  Feed cursors (`Request.pos`)
are tracked host-side — ``positions`` is a host-side mirror kept for
introspection, and the main loop's sole device→host sync per iteration
is the sampled-token fetch.

The KV cache stays in the policy's low-bit format end-to-end (the paper's
attention pipeline); weights may be offline-packed (GEMM pipeline) by
calling ``quantize_params`` before construction.

Observability costs no option.  ``step()`` runs inside a
``jax.profiler.TraceAnnotation`` named ``engine.step`` with one child per
phase (``engine.admit``, ``engine.plan``, ``engine.feed``,
``engine.dispatch``, ``engine.wait``, ``engine.emit``), which records only
while a profiler session is active; the jitted step names its device
stages with ``jax.named_scope`` (``sample`` here, the model's in its
``decode_step``).  ``Engine.stats`` (:class:`EngineStats`) counts the
work as it happens, and every output of an admitted request carries its
``queue_time``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvcache as KV
from repro.core import paged_kvcache as PKV
from repro.core.precision import PrecisionPolicy
from repro.kernels import ops as kops
from repro.models import common as C
from repro.models.registry import Model, build

from .config import EngineConfig, EngineError
from .request import (FinishReason, Request, RequestOutput, SamplingParams,
                      Status)
from .scheduler import Scheduler


# Weights that are *not* GEMM operands (gather tables, positional tables,
# tiny recurrence params) — never quantized, matching the paper's practice
# of keeping embeddings/norms high precision.
_SKIP_KEYS = ("embed", "dec_pos", "lm_head", "conv_w", "lam", "u", "w0",
              "ln", "mu_", "b1", "b2", "g", "b")


def quantize_params(params, policy: PrecisionPolicy):
    """Offline stage: run every large 2D GEMM weight through hardware-aware
    packing (paper §4.1).  Embeddings/norms/positions stay bf16."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def skip(path) -> bool:
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        return any(any(str(k).startswith(s) or str(k) == s
                       for s in _SKIP_KEYS) for k in keys)

    out = []
    for path, p in flat:
        if (not skip(path) and isinstance(p, jax.Array) and p.ndim >= 2
                and p.dtype == jnp.bfloat16):
            out.append(C.maybe_quantize(p, policy))
        else:
            out.append(p)
    return treedef.unflatten(out)


#: the engine's host phases, on the profiler's clock (a no-op without a
#: profiler session)
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class EngineStats:
    """Counts of the work ``Engine.step`` did, since construction."""

    #: model steps run, by step width (tokens fed per slot)
    steps_by_width: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: rows the steps computed (``n_slots × width`` each), and the rows
    #: among them that fed a stream token
    rows: int = 0
    valid_rows: int = 0
    #: block-table row uploads to the device (``_map_slot_blocks`` calls)
    table_uploads: int = 0
    #: paged attention kernel, per layer: (slot, block) grid cells
    #: dispatched, and the cells that hold a running slot's live context
    attn_cells: int = 0
    attn_live_cells: int = 0


def _slot_insert(batch_cache, slot_cache, slot: jax.Array):
    """Write a B=1 cache pytree into the batched cache at ``slot``.

    Every cache leaf across all families carries batch at axis 1
    (leaves are stacked (L, B, ...) by construction).  The slot cache
    may be shorter than the slab along sequence axes; the splice writes
    its extent and leaves the tail untouched (causally masked).  Used
    only by the non-chunked (recurrent / modality-stub) one-shot prefill
    path — chunked KV engines feed prompts through the main step."""
    def ins(buf, val):
        idx = (jnp.zeros((), jnp.int32), jnp.asarray(slot, jnp.int32)) + \
            tuple(jnp.zeros((), jnp.int32) for _ in range(buf.ndim - 2))
        return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype), idx)
    return jax.tree.map(ins, batch_cache, slot_cache)


class Engine:
    """Continuous-batching serving engine (see the module docstring).

    Construct with a validated :class:`EngineConfig` (and optionally
    pre-built/pre-quantized params); drive with ``submit``/``step`` or
    the ``generate``/``stream`` conveniences.  Not thread-safe: one
    engine, one driver.
    """

    def __init__(self, config: EngineConfig, params=None):
        """Build the model, quantize weights, and allocate the KV store
        (dense slab or paged pool + allocator + optional prefix index)."""
        self.config = config
        cfg = config.model
        self.model_cfg = cfg
        self.policy: PrecisionPolicy = config.policy
        self.model: Model = build(cfg)
        key = jax.random.PRNGKey(config.seed)
        raw = params if params is not None else self.model.init_params(key)
        # offline GEMM pipeline stage (no-op for w16)
        self.params = quantize_params(raw, self.policy)
        self.n_slots = config.n_slots
        self.max_seq = config.max_seq
        self.block_size = config.block_size
        self.prefill_chunk = config.prefill_chunk
        self.max_prompt = config.max_prompt
        self._extra = self.model.extra_inputs(jax.random.fold_in(key, 2), 1)
        self._has_extra = bool(self._extra)

        self._paged = config.cache_kind == "paged"
        #: on-demand growth + preemption (paged only; EngineConfig
        #: rejects the flag on dense backends)
        self._growth = self._paged and config.enable_block_growth
        self.prefix_index: Optional[PKV.PrefixIndex] = None
        if self._paged:
            # family/shape feasibility was validated by EngineConfig
            self.blocks_per_slot = config.blocks_per_slot
            self.n_blocks = config.pool_blocks
            self.allocator = PKV.BlockAllocator(self.n_blocks)
            self._block_map: Dict[int, List[int]] = {}
            self.cache = self.model.init_paged_cache(
                self.policy, self.n_slots, self.n_blocks, self.block_size,
                self.blocks_per_slot)
            gate = self._admit_gate
            if config.enable_prefix_caching:
                # the salt binds everything besides token ids that
                # determines a block's bytes: KV format and the layer
                # set / head geometry a pool block spans (DESIGN.md §5.2)
                self.prefix_index = PKV.PrefixIndex(
                    self.block_size,
                    salt=f"{cfg.name}|L{cfg.n_layers}|Hkv{cfg.n_kv_heads}"
                         f"|hd{cfg.hd}|{self.policy.kv}")
                self.allocator.on_evict = self.prefix_index.drop_block
                #: rid → (shared src block, private dst block) for a
                #: pending copy-on-write tail materialization
                self._cow_map: Dict[int, tuple] = {}
        else:
            self.cache = self.model.init_cache(self.policy, self.n_slots,
                                               self.max_seq)
            gate = None
        self.cache_kind = config.cache_kind
        self._kv_family = isinstance(
            self.cache, (KV.KVCache, PKV.PagedKVCache))
        self._chunked = self._kv_family and not self._has_extra

        self.scheduler = Scheduler(self.n_slots, admit_gate=gate)
        #: KV-transformer families decode through the Pallas multi-query
        #: flash-decode kernels (paged: in-kernel block-table
        #: indirection; dense: the slab kernel at the *same* block
        #: granularity, so the two backends traverse identical tiles and
        #: stay byte-identical) — one kernel for prefill chunks,
        #: preemption replay, and decode.  ``attn_impl="xla"`` opts any
        #: backend back onto fused XLA (useful off-TPU, where the kernels
        #: interpret); a paged xla engine gathers a transient
        #: live-context-capped dense view per step (the one remaining
        #: ``gather_view`` consumer).  Recurrent/enc-dec families keep
        #: their own decode paths.
        self._attn_kernels = (self.model.init_paged_cache is not None
                              and config.attn_impl == "kernel")
        # dense flash-decode tile height: the paged block size when it
        # divides the slab, else one whole-sequence tile
        self._flash_bs = (self.block_size
                          if self.max_seq % self.block_size == 0
                          else self.max_seq)
        #: host-side mirror of each slot's feed cursor (next KV write
        #: position), for introspection only — the jit'd step receives
        #: per-slot positions assembled fresh each iteration, and idle
        #: slots stay frozen (no drift)
        self.positions = np.zeros((self.n_slots,), np.int32)
        self._next_rid = 0
        #: live (waiting or running) requests by rid — retired/aborted
        #: requests are dropped once their final RequestOutput is emitted
        self._requests: Dict[int, Request] = {}
        #: finished outputs of directly-submitted requests that retired
        #: while generate()/stream() was driving the engine for someone
        #: else; drained (returned) by the next run_until_idle()
        self._unclaimed: List[RequestOutput] = []
        #: per-rid output queues for live stream() iterators: step()
        #: routes a subscribed rid's outputs here so interleaved streams
        #: (each driving step() on its own schedule) never lose tokens
        self._stream_bufs: Dict[int, List[RequestOutput]] = {}
        self._step = jax.jit(self._step_fn, static_argnames=("max_live",))
        self._prefill = jax.jit(self._prefill_fn)
        self._insert = jax.jit(_slot_insert)
        if self.prefix_index is not None:
            self._cow_copy = jax.jit(PKV.copy_block)
        self.t0 = time.perf_counter()
        self.iteration = 0
        self.stats = EngineStats()

    # -- jit'd inner functions -------------------------------------------

    def _prefill_fn(self, params, tokens, cache1, **extra):
        return self.model.prefill(params, self.policy, tokens, cache1,
                                  **extra)

    def _step_fn(self, params, tokens, cache, pos, valid, seeds, steps,
                 temp, top_k, max_live=None):
        """One mixed prefill/replay/decode iteration over every slot.

        tokens: (B, t_step) — slot b's next ``valid[b]`` unfed stream
        tokens (rows past that are padding; KV appends drop them and the
        sampled logits come from the last valid row).  ``t_step`` is 1
        for an all-decode batch and ``prefill_chunk`` whenever any slot
        is mid-prompt or replaying after a preemption — one jit'd
        function, two compiled shapes."""
        from . import sampler as S
        kw = {}
        if self._attn_kernels:
            kw = dict(attn_impl="pallas", attn_block_s=self._flash_bs,
                      max_live=max_live)
        elif self._paged:
            kw = dict(attn_impl="xla", max_live=max_live)
        if self._chunked:
            kw["valid"] = valid
        logits, cache = self.model.decode_step(params, self.policy, tokens,
                                               cache, pos, **kw)
        with jax.named_scope("sample"):
            nxt = S.sample(S.slot_keys(seeds, steps), logits, temp, top_k)
        return nxt, cache

    # -- public API --------------------------------------------------------

    def now(self) -> float:
        """Monotonic seconds since engine construction (metric clock)."""
        return time.perf_counter() - self.t0

    def submit(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               arrival_time: Optional[float] = None) -> int:
        """Enqueue a request; returns its rid (the handle for ``abort``
        and for matching ``step()`` outputs).  Inadmissible requests are
        rejected here with :class:`EngineError` — a clean typed refusal,
        never a mid-decode crash."""
        prompt = list(prompt)
        if not prompt:
            raise EngineError("prompt must contain at least one token")
        if len(prompt) > self.max_prompt:
            raise EngineError(
                f"prompt length {len(prompt)} exceeds max_prompt="
                f"{self.max_prompt}")
        params = params or SamplingParams()
        req = Request(rid=self._next_rid, prompt=prompt, params=params,
                      arrival_time=self.now() if arrival_time is None
                      else arrival_time,
                      seed=self._resolve_seed(params, self._next_rid))
        if self._paged and self._blocks_for(req) > self.n_blocks:
            # infeasible even with the whole pool free: reject now rather
            # than deadlock the FCFS queue behind an unadmittable head.
            # The growth engine keeps this *worst-case* check too: a
            # request that outgrows the whole pool would preempt every
            # sibling and then livelock alone at the queue head
            raise EngineError(
                f"request needs {self._blocks_for(req)} KV blocks "
                f"(prompt {len(req.prompt)} + max_new "
                f"{req.params.max_new_tokens}) but the pool has only "
                f"{self.n_blocks}")
        self._next_rid += 1
        self._requests[req.rid] = req
        self.scheduler.add(req)
        return req.rid

    def abort(self, rid: int) -> Optional[RequestOutput]:
        """Cancel a request.  A waiting request leaves the queue; a
        running request frees its slot immediately and (paged) returns its
        KV blocks to the pool.  Returns the final ``finish_reason="abort"``
        output, or None if the rid is unknown or already finished (abort
        is idempotent).  Aborted requests emit nothing from ``step()``."""
        req = self._requests.get(rid)
        if req is None:
            return None
        if req.status in (Status.WAITING, Status.PREEMPTED):
            self.scheduler.remove_waiting(req)
            req.status = Status.FINISHED
            req.finish_time = self.now()
            # paged: waiting requests hold no blocks (reservation happens
            # at admission) and preempted requests already released
            # theirs, so there is nothing to reclaim
        else:
            self.scheduler.finish(req, self.now())
            if self._paged:
                self._reclaim(req)
            # the freed slot's device state needs no scrub: stale KV is
            # causally masked and the next occupant's admission resets
            # the slot's feed cursor
        req.finish_reason = FinishReason.ABORT
        del self._requests[rid]
        return req.make_output([])

    def _resolve_seed(self, params: SamplingParams, rid: int) -> int:
        """Explicit ``params.seed`` wins; otherwise derive a fresh
        per-submission stream from the engine seed and rid."""
        if params.seed is not None:
            return int(params.seed) & 0x7FFFFFFF
        return ((self.config.seed * 1_000_003) ^ (rid * 0x9E3779B1)) \
            & 0x7FFFFFFF

    # -- paged bookkeeping -------------------------------------------------

    def _blocks_for(self, req: Request) -> int:
        """Worst-case KV blocks for a request: prompt minus the last token
        (re-decoded) plus every potential output token, clipped to the
        context limit.  In reservation mode (the default) this is pinned
        whole at admission so a running request can never stall
        mid-decode for want of a block; in growth mode it is only the
        feasibility ceiling (``submit`` rejection / headroom clip)."""
        toks = min(len(req.prompt) - 1 + req.params.max_new_tokens,
                   self.max_seq)
        return PKV.blocks_needed(max(toks, 1), self.block_size)

    def _admission_blocks(self, req: Request) -> int:
        """Blocks pinned at admission.  Reservation mode: the worst case
        (:meth:`_blocks_for`).  Growth mode: just the *effective*
        sequence — prompt plus any tokens already produced before a
        preemption (the replay rewrites their KV) plus one position for
        the first decode append — padded by ``reserve_headroom_blocks``
        and never more than the worst case."""
        if not self._growth:
            return self._blocks_for(req)
        eff = min(len(req.prompt) + len(req.output), self.max_seq)
        need = PKV.blocks_needed(max(eff, 1), self.block_size)
        return min(need + self.config.reserve_headroom_blocks,
                   self._blocks_for(req))

    def _match_prefix(self, req: Request):
        """Longest cached block chain matching the request's prompt.

        Returns ``(shared, cow_src)``: ``shared`` are read-only-shareable
        full blocks — they cover prompt tokens the slot will never write
        (everything strictly below the decode frontier ``n - 1``) —
        and ``cow_src`` is the at-most-one matched block the slot *would*
        append into (the block holding position ``n - 1``, matched only
        when the prompt length is block-aligned): it must be materialized
        copy-on-write, never mapped shared."""
        if self.prefix_index is None:
            return [], None
        req.prefix_hashes = self.prefix_index.chain_hashes(req.prompt)
        matched = self.prefix_index.match_chain(req.prefix_hashes)
        ro = (len(req.prompt) - 1) // self.block_size
        return matched[:ro], (matched[ro] if len(matched) > ro else None)

    def _admit_gate(self, req: Request) -> bool:
        """Admission gate with *reservation* semantics: returning True
        also allocates the request's worst-case blocks, so admitting
        several requests in one scheduler pass can never over-commit the
        pool (each gate call sees the allocator state left by the
        previous admission).

        With prefix caching, matched blocks are mapped shared (one more
        reference on the same physical block) and only the remainder is
        allocated — a prefix hit admits where a cold request would have
        been deferred.  The COW source is pinned (shared) until
        ``_admit`` finishes the copy, so a sibling admission's
        eviction can never race it away.

        In growth mode the reservation covers only the effective
        sequence plus headroom (:meth:`_admission_blocks`) — decode
        grows the mapping block by block (:meth:`_grow_for_step`)."""
        need = self._admission_blocks(req)
        shared, cow_src = self._match_prefix(req)
        pinned = shared + ([cow_src] if cow_src is not None else [])
        for b in pinned:
            self.allocator.share(b)
        if cow_src is not None and \
                not self.allocator.can_alloc(need - len(shared)):
            # the COW source is a *transient* extra block (pinned only
            # until the copy lands); when that +1 doesn't fit, degrade
            # the COW tail to a recomputed miss rather than defer a
            # request the unshared engine would admit (no livelock:
            # nothing else may ever free the missing block)
            self.allocator.free([cow_src])
            cow_src = None
            pinned = shared
        if not self.allocator.can_alloc(need - len(shared)):
            self.allocator.free(pinned)      # unpin: admission deferred
            return False
        fresh = self.allocator.alloc(need - len(shared))
        self._block_map[req.rid] = shared + fresh
        if self.prefix_index is not None:
            bs = self.block_size
            if cow_src is not None:
                # the COW destination is the first fresh block: logical
                # index len(shared), the block holding position n - 1
                self._cow_map[req.rid] = (cow_src, fresh[0])
                req.prefix_skip = len(req.prompt) - 1
                # the re-decoded last prompt token is honest recompute
                req.cached_tokens = len(shared) * bs + (bs - 1)
            else:
                req.prefix_skip = req.cached_tokens = len(shared) * bs
        return True

    def _map_slot_blocks(self, slot: int, blocks: List[int]) -> None:
        self.stats.table_uploads += 1
        row = jnp.full((self.blocks_per_slot,), self.n_blocks, jnp.int32)
        if blocks:
            row = row.at[:len(blocks)].set(jnp.asarray(blocks, jnp.int32))
        tbl = self.cache.block_table.at[:, slot].set(row)
        self.cache = dataclasses.replace(self.cache, block_table=tbl)

    def _register_prefix(self, req: Request) -> None:
        """Publish the slot's immutable full prompt blocks in the prefix
        index: every block strictly below the decode frontier ``n - 1``
        is fully written by prefill and never touched again, so its bytes
        are safe to share for the rest of its lifetime.  Blocks that were
        themselves mapped from the index re-register as no-ops; a lost
        register race (an identical prompt admitted in the same scheduler
        pass) leaves the duplicate block private — correct, just not
        deduplicated."""
        nb = (len(req.prompt) - 1) // self.block_size
        # chain hashes were computed once at the admission gate; the
        # chain property makes hashes[:nb] exactly the truncated prompt's
        for h, b in zip(req.prefix_hashes[:nb],
                        self._block_map[req.rid][:nb]):
            if self.prefix_index.register(h, b):
                self.allocator.set_cacheable(b)

    def _reclaim(self, req: Request) -> None:
        """Release the request's block references.  Without sharing this
        frees the blocks outright; with sharing it decrefs — blocks other
        slots still map stay live, and index-published blocks park on the
        allocator's CACHED LRU for future prefix hits."""
        self.allocator.free(self._block_map.pop(req.rid))
        self._map_slot_blocks(req.slot, [])   # sentinel row: writes dropped

    def _preempt(self, req: Request) -> None:
        """Evict a running request to recover pool blocks (growth mode).

        Its block references are released (shared blocks stay live for
        their other holders; index-published blocks park on the CACHED
        LRU — which is what lets prefix caching soften the recompute),
        its slot frees, and it requeues at the *front* of the waiting
        queue as ``Status.PREEMPTED``.  Its produced tokens are kept:
        re-admission re-feeds its whole stream (prompt + produced) in
        forced multi-token chunks, byte-exactly (see ``_admit`` /
        ``step``).  The eviction timestamp opens the recovery-latency
        window closed at the request's next emission."""
        req.num_preemptions += 1
        if req.recovery_started is None:
            req.recovery_started = self.now()
        self._reclaim(req)            # while req.slot is still valid
        self.scheduler.preempt(req)

    def _grow_for_step(self, running: List[Request],
                       valids: Dict[int, int]) -> List[Request]:
        """Growth-mode pre-step pass: make sure every running slot's
        next append (positions ``req.pos .. req.pos + valid - 1``) lands
        in mapped blocks.

        Walks the batch oldest-first (rid order) and allocates one block
        per boundary crossing.  When the pool cannot cover a block —
        FREE and evictable CACHED both exhausted — the *youngest*
        running request is preempted (possibly the requester itself:
        self-preemption is the vLLM recompute discipline) until the
        allocation fits.  Oldest-first growth + youngest-first eviction
        makes priority acyclic, so the oldest request always progresses
        and the loop terminates.  Returns the surviving running set."""
        bs = self.block_size
        for req in sorted(running, key=lambda r: r.rid):
            end = req.pos + valids[req.rid]   # one past the last write
            while (req.status == Status.RUNNING
                   and end > len(self._block_map[req.rid]) * bs):
                if self.allocator.can_alloc(1):
                    blocks = self._block_map[req.rid]
                    blocks.extend(self.allocator.alloc(1))
                    self._map_slot_blocks(req.slot, blocks)
                else:
                    self._preempt(self.scheduler.victim())
        return self.scheduler.running()

    def _live_bucket(self, running) -> int:
        """Static live-context bound for the paged decode kernel: the
        batch's high-water mark ``max(pos) + 1`` rounded up to whole
        blocks and then to a power-of-two block count (so the number of
        distinct decode compilations is O(log blocks_per_slot), not one
        per context length), clipped to ``max_context``."""
        hw = max(r.pos for r in running) + 1
        nb = PKV.blocks_needed(hw, self.block_size)
        nb = 1 << (nb - 1).bit_length()
        return min(nb, self.blocks_per_slot) * self.block_size

    # -- admission ---------------------------------------------------------

    def _admit(self, req: Request) -> None:
        """Install one admitted request into its slot.

        Chunked KV families do **no prompt compute here**: the request's
        feed cursor is seeded at the prefix-cache skip and ``step()``
        feeds the prompt through the batched multi-token kernel step,
        quantize-and-writing each chunk straight into the slot's pool
        blocks / slab rows (pool-direct prefill — no staging cache, no
        splice).  On a prefix-cache hit the slot's table already maps
        the shared blocks (the gate set them up), so attention over the
        skipped extent reads bytes bit-identical to a cold prefill; a
        pending copy-on-write tail is materialized first (device block
        copy; the pinned source is released once copied).  Prefix
        registration waits for the request's first emission, when every
        block below the frontier is fully written.

        Emission protocol (unchanged): the last prompt token's step
        produces the first output token — at the k-th emission the feed
        cursor sits at ``n - 1 + k``, exactly the dense engine's
        historical position arithmetic, so room/finish logic is shared.

        Recurrent-state and modality-stub families keep their one-shot
        exact-length prefill: no multi-token decode path (or prefill
        consumes extra encoder inputs), so the prompt minus its last
        token runs through ``model.prefill`` into a B=1 cache spliced
        into the slot."""
        n = len(req.prompt)
        if self._paged:
            # blocks were reserved by the admission gate
            self._map_slot_blocks(req.slot, self._block_map[req.rid])
            if self.prefix_index is not None:
                cow = self._cow_map.pop(req.rid, None)
                if cow is not None:
                    src, dst = cow
                    self.cache = self._cow_copy(self.cache, jnp.int32(src),
                                                jnp.int32(dst))
                    self.allocator.free([src])     # unpin the COW source
        if self._chunked:
            # feed everything from the prefix frontier on — including
            # any output produced before a preemption (its blocks are
            # gone; the forced chunks rewrite their KV byte-exactly)
            req.pos = req.prefix_skip
            req.needs_register = self.prefix_index is not None
            self.positions[req.slot] = req.pos
            return
        if n > 1 or self._has_extra:
            # one-shot exact-length prefill: recurrent-state families (no
            # multi-token decode) and modality-stub families (extra
            # encoder inputs are consumed by prefill).  P >= 1 keeps
            # encoder caches built even for single-token prompts.
            # Exact length means one XLA compile per distinct prompt
            # length — correctness over compile count: padding would
            # pollute recurrent state.  KV families stay shape-bounded
            # via chunking.
            P = max(n - 1, 1)
            toks = jnp.asarray(req.prompt[:P], jnp.int32)[None]
            cache1 = self.model.init_cache(self.policy, 1, self.max_seq)
            _, cache1 = self._prefill(self.params, toks, cache1,
                                      **self._extra)
            self.cache = self._insert(self.cache, cache1, req.slot)
        elif not self._kv_family:
            # single-token prompt into a recurrent family: reset the
            # slot's state (stale state is not masked by any causal mask)
            cache1 = self.model.init_cache(self.policy, 1, self.max_seq)
            self.cache = self._insert(self.cache, cache1, req.slot)
        req.pos = n - 1
        self.positions[req.slot] = req.pos

    # -- main loop ---------------------------------------------------------

    def _has_room(self, req: Request) -> bool:
        """True while the slot can absorb another decode append (uses the
        host-side position mirror — no device sync).

        The context-limit guard (``pos < max_seq - 1``) is shared by both
        backends; paged slots in *reservation* mode additionally require
        the next write to land inside the blocks reserved at admission —
        by construction that never binds before ``max_new_tokens`` does,
        so the two backends retire requests on identical iterations.  In
        *growth* mode the mapping extends on demand, so room is bounded
        by ``max_seq`` / ``blocks_per_slot`` alone (the first guard:
        ``max_seq == blocks_per_slot * block_size`` for paged configs) —
        never by the current reservation."""
        if req.pos >= self.max_seq - 1:
            return False
        if self._paged and not self._growth:
            cap = len(self._block_map[req.rid]) * self.block_size
            return req.pos < cap
        return True

    def _finish_reason(self, req: Request, tok: int) -> \
            Optional[FinishReason]:
        """Retirement decision for the token just produced.  eos/stop are
        suppressed until ``min_new_tokens`` have been produced; the length
        cap and context exhaustion always bind."""
        produced = len(req.output)
        reason = None
        if produced >= req.params.min_new_tokens:
            reason = req.params.stops_on(tok)
        if reason is None and produced >= req.params.max_new_tokens:
            reason = FinishReason.LENGTH
        if reason is None and not self._has_room(req):
            reason = FinishReason.CONTEXT
        return reason

    def step(self) -> List[RequestOutput]:
        """One engine iteration: admit waiting requests, feed every
        running slot its next stream tokens through one batched kernel
        step, retire finished requests.

        Each request's prompt + produced output is one logical token
        stream; ``Request.pos`` counts how much of it has been fed.  The
        scheduler's plan picks the step width: 1 when every slot is in
        steady-state decode, ``prefill_chunk`` when any slot is
        mid-prompt or recovering from a preemption — prefill chunks and
        decode rows share the batch (decode rows carry ``valid == 1``,
        their padding dropped by the ragged mask), so a request's stream
        is invariant to what else shares the batch *and* to the chunk
        partition.  A slot emits a token only on the iteration that
        consumes its last unfed stream token; iterations that re-feed
        already-streamed output after a preemption count as
        ``replay_iterations`` — O(produced / prefill_chunk) per
        preemption, not O(produced).

        Returns one :class:`RequestOutput` per *emitting* request — a
        delta of exactly one new token plus the cumulative output;
        finished requests carry ``finish_reason`` and final timing
        metrics.  Growth mode may additionally grow/preempt before the
        step (preempted requests emit nothing until recovered)."""
        with _span("engine.step"):
            self.iteration += 1
            with _span("engine.admit"):
                now = self.now()
                for req in self.scheduler.admit():
                    if req.admit_time is None:
                        req.admit_time = now
                    self._admit(req)
            running = self.scheduler.running()
            if not running:
                return []
            with _span("engine.plan"):
                running, t_step, valids = self._plan(running)
            if not running:
                return []
            with _span("engine.feed"):
                tokens, pos, valid, seeds, steps, temp, top_k, max_live = \
                    self._feed(running, t_step, valids)
            with _span("engine.dispatch"):
                nxt, self.cache = self._step(self.params, tokens, self.cache,
                                             pos, valid, seeds, steps, temp,
                                             top_k, max_live=max_live)
                t = self.now()
            with _span("engine.wait"):
                nxt_host = np.asarray(jax.device_get(nxt))
            with _span("engine.emit"):
                return self._emit(running, valids, nxt_host, t)

    def _plan(self, running: List[Request]):
        """The step's width and per-request feed counts; growth mode
        first maps (or preempts for) the blocks the step will write.
        Returns (surviving running set, width, {rid: valid})."""
        chunk = self.prefill_chunk if self._chunked else 1
        t_step, valids = self.scheduler.plan(chunk)
        if self._growth:
            # lazy growth (and any preemption it forces) runs *before*
            # the batched step, so every surviving slot's appends land
            # in mapped blocks — sentinel-dropped writes would silently
            # corrupt the new tokens' own attention reads.  Preemption
            # shrinks the running set, so re-plan (the step may narrow
            # back to width 1).
            running = self._grow_for_step(running, valids)
            if running:
                t_step, valids = self.scheduler.plan(chunk)
        return running, t_step, valids

    def _feed(self, running: List[Request], t_step: int,
              valids: Dict[int, int]):
        """The jitted step's per-slot inputs and its live-context bound;
        counts the step in :attr:`stats`."""
        # per-slot feed + sampling vectors, assembled host-side (numpy)
        # and handed to the jit'd step as single transfers — no
        # per-request scatter dispatches in the hot loop.  Idle slots
        # feed token 0 at position 0 with valid == 0: their writes are
        # dropped and their sampled logits discarded.
        tokens = np.zeros((self.n_slots, t_step), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        valid = np.zeros((self.n_slots,), np.int32)
        temp = np.zeros((self.n_slots,), np.float32)
        top_k = np.zeros((self.n_slots,), np.int32)
        seeds = np.zeros((self.n_slots,), np.uint32)
        steps = np.zeros((self.n_slots,), np.int32)
        for r in running:
            v = valids[r.rid]
            stream = r.prompt + r.output
            tokens[r.slot, :v] = stream[r.pos:r.pos + v]
            pos[r.slot] = r.pos
            valid[r.slot] = v
            temp[r.slot] = r.params.temperature
            top_k[r.slot] = r.params.top_k
            seeds[r.slot] = r.seed
            steps[r.slot] = len(r.output)

        # paged: bound the kernel's grid (and its HBM traffic) by the
        # batch's live-context high-water mark, not worst-case max_seq
        max_live = self._live_bucket(running) if self._paged else None
        st = self.stats
        st.steps_by_width[t_step] = st.steps_by_width.get(t_step, 0) + 1
        st.rows += self.n_slots * t_step
        st.valid_rows += int(valid.sum())
        if self._paged and self._attn_kernels:
            bs = self.block_size
            n_s = min(kops.grid_blocks(max_live, t_step, bs),
                      self.blocks_per_slot)
            st.attn_cells += self.n_slots * n_s
            st.attn_live_cells += sum(
                min(-(-(r.pos + valids[r.rid]) // bs), n_s) for r in running)
        return (jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(valid),
                seeds, steps, temp, top_k, max_live)

    def _emit(self, running: List[Request], valids: Dict[int, int],
              nxt_host: np.ndarray, t: float) -> List[RequestOutput]:
        """Advance every fed request's cursor; emit, retire and reclaim
        those that consumed their last unfed stream token."""
        outputs: List[RequestOutput] = []
        for r in running:
            r.pos += valids[r.rid]
            self.positions[r.slot] = r.pos
            if r.pos < len(r.prompt) + len(r.output):
                # non-emitting: the prompt is still prefilling, or a
                # preempted request is re-feeding tokens it already
                # streamed (forced, not sampled — byte-exact recovery)
                if r.pos > len(r.prompt):
                    r.replay_iterations += 1
                continue
            tok = int(nxt_host[r.slot])
            if r.first_token_time is None:
                r.first_token_time = t
            if r.recovery_started is not None:
                # eviction → this emission: the stream is caught up
                r.recovery_time += t - r.recovery_started
                r.recovery_started = None
            if r.needs_register:
                # first emission: every block below the frontier is now
                # fully written — safe to publish in the prefix index
                self._register_prefix(r)
                r.needs_register = False
            r.output.append(tok)
            reason = self._finish_reason(r, tok)
            if reason is not None:
                r.finish_reason = reason
                self.scheduler.finish(r, t)
                if self._paged:
                    self._reclaim(r)
                del self._requests[r.rid]
            out = r.make_output([tok])
            outputs.append(out)
            if r.rid in self._stream_bufs:
                self._stream_bufs[r.rid].append(out)
        return outputs

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Union[SamplingParams, Sequence[SamplingParams],
                               None] = None,
                 max_iters: int = 100_000) -> List[RequestOutput]:
        """Batch convenience: submit every prompt, drive ``step()`` until
        all of them finish, return their final outputs in prompt order.
        ``params`` is one shared :class:`SamplingParams` or one per
        prompt.  All-or-nothing: if any prompt is inadmissible, nothing
        is enqueued (no orphaned requests behind the raised
        :class:`EngineError`)."""
        if params is None or isinstance(params, SamplingParams):
            params = [params] * len(prompts)
        if len(params) != len(prompts):
            raise EngineError(
                f"got {len(params)} SamplingParams for "
                f"{len(prompts)} prompts")
        rids: List[int] = []
        try:
            for p, sp in zip(prompts, params):
                rids.append(self.submit(p, sp))
        except EngineError:
            for rid in rids:
                self.abort(rid)
            raise
        pending = set(rids)
        final: Dict[int, RequestOutput] = {}
        for _ in range(max_iters):
            if not pending:
                return [final[rid] for rid in rids]
            for out in self.step():
                if not out.finished:
                    continue
                if out.rid in pending:
                    final[out.rid] = out
                    pending.discard(out.rid)
                elif out.rid not in self._stream_bufs:
                    self._unclaimed.append(out)
        raise RuntimeError("generate() did not drain")

    def stream(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None,
               max_iters: int = 100_000) -> Iterator[RequestOutput]:
        """Incremental convenience: submit one prompt and yield its
        :class:`RequestOutput` snapshots (one new token each) as decode
        iterations complete, until it finishes.  Driving the iterator
        advances the whole engine, so concurrent requests keep decoding;
        outputs for *other* live streams are queued to their iterators
        (interleaving streams never loses tokens) and finished outputs of
        directly-submitted requests land in the unclaimed buffer — see
        :meth:`run_until_idle`.  If the request is ``abort()``-ed
        mid-stream the iterator simply ends (the abort caller got the
        final output).  An *abandoned* iterator (the caller breaks out /
        drops it, closing the generator) aborts its own request, so the
        slot and its KV blocks return to the pool immediately instead of
        leaking until some other driver happens to drain it."""
        rid = self.submit(prompt, params)
        buf = self._stream_bufs.setdefault(rid, [])
        try:
            for _ in range(max_iters):
                while buf:
                    out = buf.pop(0)
                    yield out
                    if out.finished:
                        return
                if rid not in self._requests:
                    return
                for out in self.step():
                    if out.finished and out.rid not in self._stream_bufs \
                            and out.rid != rid:
                        self._unclaimed.append(out)
            raise RuntimeError("stream() did not finish")
        except GeneratorExit:
            # caller closed the iterator mid-stream: without this the
            # request would stay RUNNING, holding its slot and blocks
            # forever.  abort() is idempotent — a no-op if the request
            # already finished between the last yield and the close.
            self.abort(rid)
            raise
        finally:
            self._stream_bufs.pop(rid, None)

    def run_until_idle(self, max_iters: int = 10_000) -> List[RequestOutput]:
        """Drive ``step()`` until no request is waiting or running;
        returns the finished outputs in completion order — including any
        *unclaimed* finals (requests the caller submitted directly that
        happened to finish while a ``generate()``/``stream()`` call was
        driving the engine)."""
        finished, self._unclaimed = self._unclaimed, []
        for _ in range(max_iters):
            if self.scheduler.idle:
                return finished
            finished.extend(o for o in self.step() if o.finished
                            and o.rid not in self._stream_bufs)
        raise RuntimeError("engine did not drain")

    # -- introspection -----------------------------------------------------

    def kv_resident_bytes(self) -> int:
        """Resident bytes of the KV store (pool/slab + scales + tables)."""
        return PKV.kv_bytes(self.cache)


def percentile_stats(vals: List[float]) -> Dict[str, float]:
    """p50/p90/p95/p99 of a metric list ({} when empty)."""
    if not vals:
        return {}
    a = np.asarray(vals)
    return {f"p{p}": float(np.percentile(a, p)) for p in (50, 90, 95, 99)}
