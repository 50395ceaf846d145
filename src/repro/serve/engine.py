"""The serving engine: scheduler / KV pool / executor, continuous batching.

The engine is three explicit layers (``docs/serving_disagg.md``):

* :class:`repro.serve.scheduler.Scheduler` — the **policy** layer: request
  queue (arrival ticks, priorities, tenants) and per-tick admission.
  Continuous batching means admission happens *every decode tick* into any
  free slot, not only between whole batches; the same policy object drives
  the disagg control window's fetch_op ticket budget
  (:func:`repro.serve.disagg.claim_slots`).
* :class:`repro.serve.paged.KVPoolManager` — the **pool** layer: refcounts
  on physical KV pages, copy-on-write prefix sharing (sequences with a
  common prompt prefix map the *same* physical pages and fork only on the
  first divergent write), FIFO free list, double-free guards.
* :class:`Executor` (here) — the **execution** layer: owns the batched
  device cache and the jitted prefill/decode, and runs exactly what the
  scheduler admitted this tick.  It knows nothing about queues or
  refcounts; the facade hands it slots, physical pages, and a write mask.

:class:`ServeEngine` is the facade wiring the three together, keeping the
original public surface (``submit`` / ``step`` / ``run`` / ``stats``,
``slot_free`` / ``slot_req`` / ``done``).  Greedy decode is bit-identical
to the previous monolithic engine — the layers change who decides, not
what runs.

``paged_kv=True`` replaces the dense per-slot KV with the **paged pool
layout** (``models/paged_cache.py``, which owns it and every operation on
it): the self-attention cache becomes a physical page pool plus a per-row
page table — exactly the cache a decode worker owns in a prefill→decode
split.  The executor's page ops are single calls into that module.
``prefix_share=True`` additionally admits new requests onto the pages of a
live request with a common prompt prefix:

* full pages entirely inside the common prefix are mapped **immutably**
  (refcount+1, write-protected device-side via the cache's ``page_ro``
  leaf — decode scatters at them are dropped like overflow writes);
* the one partial page at the prefix boundary is mapped **copy-on-write**
  when the new prompt ends exactly at the prefix (both holders will write
  it): the engine forks it — device page copy + table remap — the tick a
  holder's write position reaches it while the refcount is still > 1.

Sharing is safe on two grounds: KV at position *i* depends only on tokens
``0..i`` (identical prefixes ⇒ bit-identical pages, prefilled by the same
jitted function), and decode is write-then-attend (a forked copy's stale
positions are overwritten before their causal mask ever opens).  The
pool's :meth:`~repro.serve.paged.KVPoolManager.can_admit` reserves one
free page per outstanding writable share, so a fork can never find the
free list empty.

``kv_pages=(hbm_pages, host_pages)`` turns the pool into a **tiered
memory hierarchy** (``docs/serving_disagg.md``): admission is priced
against HBM + host capacity (so more sequences are live than HBM alone
could back) while the per-tick decode set is priced against HBM only.
Live slots rotate through the tiers — inactive slots' pages are demoted
to a host-memory :class:`~repro.serve.paged.HostKVTier` window via
planned puts, and promotions are scheduled a tick ahead so the planned
gets ride **prefetch edges** overlapped with the demote traffic
(:func:`~repro.serve.paged.tier_step_plan`).  Only active slots commit
tokens each tick; because greedy decode is row-independent and a
promotion restores the slot's pages, table row, and position exactly,
the committed token streams are bit-identical to the all-HBM engine.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import paged_cache
from repro.models.transformer import moe_counts
from repro.serve.paged import HostKVTier, KVPoolManager
from repro.serve.scheduler import Scheduler

Array = jax.Array


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int
    eos_id: int = -1            # -1: never stops early
    priority: int = 0           # policy="priority": higher admits first
    tenant: int = 0             # policy="fair": fair-share key


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list
    finished: bool = True       # False: run() ran out of ticks (partial)
    arrival_tick: int = 0
    done_tick: int = 0
    # host times (time.perf_counter; 0.0 where not reached), as on SchedEntry
    t_submit: float = 0.0
    t_admit: float = 0.0        # its prefill dispatched
    t_first: float = 0.0        # its first token read on the host
    t_out: float = 0.0          # the end of the tick that made that token


_NO_SPAN = contextlib.nullcontext()


def _span(name: str, **args):
    """A host span in the profiler's trace, named ``serve.*``, with integer
    ``args``.  While no trace runs it is a shared no-op, well under a
    microsecond; call it only outside jitted code."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name, **args)
    return _NO_SPAN


def _insert_row(full: Array, one: Array, slot, n_slots: int) -> Array:
    """Scatter a 1-row leaf into the n_slots-row leaf along the batch axis.

    The batch axis is wherever `one` is 1 and `full` is n_slots with all
    other dims equal (scan-stacked leaves carry a leading layers dim, so it
    is not always axis 0)."""
    if full.ndim != one.ndim:
        return full
    for ax in range(full.ndim):
        rest_f = full.shape[:ax] + full.shape[ax + 1:]
        rest_o = one.shape[:ax] + one.shape[ax + 1:]
        if (one.shape[ax] == 1 and full.shape[ax] == n_slots
                and rest_f == rest_o):
            starts = [0] * full.ndim
            starts[ax] = slot
            return jax.lax.dynamic_update_slice(
                full, one.astype(full.dtype), tuple(starts))
    return full


def executor_programs(model, *, n_slots: int, max_seq: int, enc_len: int = 0,
                      paged_kv: bool = False, page_tokens: int = 16):
    """The executor's programs, jitted: ``(fresh_cache, decode_step,
    prefill_into_slot)``.

    ``fresh_cache()`` builds the batched cache in one program (paged, the
    pools are made in place, never the dense cache and its re-paged copy
    side by side on the device).  ``decode_step(params, cache, tokens)`` and
    ``prefill_into_slot(params, cache, tokens, slot, phys_pages, write_ok)``
    return ``(logits, cache, counts)``: ``counts`` is the call's expert
    counters (``transformer.moe_counts``), or None for a model without an
    expert share.  Both **donate** the cache: the page pools are written in
    place, and the cache passed in is dead once the call is made."""
    def fresh_cache():
        cache = model.init_cache(n_slots, max_seq, enc_len=enc_len)
        if paged_kv:
            cache = paged_cache.paginate_cache(cache, page_tokens)
        return cache

    def decode_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        return logits, cache, moe_counts(cache)

    # single-sequence prefill that scatters into one cache slot; in paged
    # mode the dense prefill KV is re-paged into the slot's physical pages
    # (write-masked pages land on the parking page — they are shared, their
    # contents already prefilled by the donor) and the slot's page-table
    # row is wired up
    def prefill_into_slot(params, cache, tokens, slot, phys_pages, write_ok):
        sub = model.init_cache(1, max_seq, enc_len=enc_len)
        logits, sub = model.prefill(params, {"tokens": tokens}, sub)
        cache = _insert(cache, sub, slot, phys_pages, write_ok, n_slots)
        return logits, cache, moe_counts(sub)

    return (jax.jit(fresh_cache), jax.jit(decode_step, donate_argnums=1),
            jax.jit(prefill_into_slot, donate_argnums=1))


def _insert(full, one, slot, phys_pages, write_ok, n_slots: int):
    """Insert the freshly prefilled 1-row cache ``one`` into slot ``slot``
    of the engine cache ``full`` (recursive walk; paged attention dicts
    scatter through the page table, everything else along the batch
    axis)."""
    if isinstance(full, dict):
        if paged_cache.is_paged(full):
            return paged_cache.insert_prefill(full, one, slot, phys_pages,
                                              write_ok)
        return {key: _insert(full[key], one[key], slot, phys_pages, write_ok,
                             n_slots)
                for key in full}
    if isinstance(full, list):
        return [_insert(f, o, slot, phys_pages, write_ok, n_slots)
                for f, o in zip(full, one)]
    return _insert_row(full, one, slot, n_slots)


class Executor:
    """The execution layer: batched cache + jitted prefill/decode.

    Decisions live elsewhere — the scheduler picks *what* runs, the pool
    manager picks *which pages* back it; the executor is handed a slot, a
    physical-page row, and a per-page write mask, and runs the model.

    The cache is donated to every program that returns it
    (:func:`executor_programs`): ``self.cache`` is replaced by each call's
    result, and no caller may keep a reference to it across ``prefill`` or
    ``decode``."""

    def __init__(self, model, params, *, n_slots: int, max_seq: int,
                 enc_len: int = 0, paged_kv: bool = False,
                 page_tokens: int = 16):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        self.paged_kv = paged_kv
        fresh_cache, self._decode_jit, self._prefill_fn = executor_programs(
            model, n_slots=n_slots, max_seq=max_seq, enc_len=enc_len,
            paged_kv=paged_kv, page_tokens=page_tokens)
        self.cache = fresh_cache()
        if paged_kv and not paged_cache.pool_leaves(self.cache):
            raise ValueError(
                f"paged_kv=True but the {model.cfg.family!r} stack has "
                "no self-attention KV caches to page (SSM caches stay "
                "dense) — the paged data plane would be a no-op")
        # a model whose expert layers hold a share of the experts counts,
        # per call, the assignments routed to held experts and the held
        # experts hit (``transformer.moe_counts``); they come back with the
        # tokens, in the same host sync
        self.counted = moe_counts(self.cache) is not None
        self.last_counts = None      # (moe_held, experts_hit) of the last call
        self.prefill_shapes: set[tuple] = set()   # one compile each
        # the decode program's one shape is compiled once, on first use, and
        # that executable runs every step (its memory analysis is read
        # without a second compile)
        self._decode_exe = None

    def _decode_fn(self, params, cache, tokens):
        if self._decode_exe is None:
            self._decode_exe = self._decode_jit.lower(params, cache,
                                                      tokens).compile()
        return self._decode_exe(params, cache, tokens)

    @property
    def pool_bytes(self) -> int:
        """Bytes of the cache's page pools (0 unpaged)."""
        return sum(d[pool].nbytes
                   for d, pool, _ in paged_cache.pool_leaves(self.cache))

    @property
    def decode_pool_aliased_bytes(self) -> int | None:
        """Bytes of the decode program's inputs its outputs reuse in place
        (``memory_analysis().alias_size_in_bytes``), or None before the
        first decode compiled it."""
        if self._decode_exe is None:
            return None
        return self._decode_exe.memory_analysis().alias_size_in_bytes

    # -- the two model calls ----------------------------------------------------
    def prefill(self, tokens: Array, slot: int, phys_pages: Array,
                write_ok: Array) -> int:
        """Prefill one admitted request into ``slot``; returns its first
        greedy token."""
        self.prefill_shapes.add(tuple(tokens.shape))
        logits, self.cache, counts = self._prefill_fn(
            self.params, self.cache, tokens, slot, phys_pages, write_ok)
        argmax = jnp.argmax(logits[0, -1])
        with _span("serve.prefill.sync"):
            if not self.counted:
                return int(np.asarray(argmax))
            argmax, self.last_counts = jax.device_get((argmax, counts))
            return int(argmax)

    def decode(self, last_tokens: np.ndarray, **span_args) -> np.ndarray:
        """One decode step over every slot; returns per-slot argmax.  A
        counted model's span gains its ``moe_held`` and ``experts_hit``."""
        tokens = jnp.asarray(last_tokens)
        span = _span("serve.decode", **span_args)
        with span:
            logits, self.cache, counts = self._decode_fn(self.params,
                                                         self.cache, tokens)
            argmax = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            with _span("serve.decode.sync"):
                if not self.counted:
                    return np.asarray(argmax)
                argmax, self.last_counts = jax.device_get((argmax, counts))
            if span is not _NO_SPAN:
                span.set_metadata(moe_held=int(self.last_counts[0]),
                                  experts_hit=int(self.last_counts[1]))
            return argmax

    # -- paged-pool device ops (``models/paged_cache.py``) ----------------------
    def fork_page(self, slot: int, j: int, src: int, dst: int) -> None:
        """Copy-on-write fork of page ``src`` to ``dst`` for ``slot``'s
        entry ``j`` (:func:`paged_cache.fork_page`)."""
        self.cache = paged_cache.fork_page(self.cache, slot, j, src, dst)

    def set_pages_ro(self, pages, value: bool) -> None:
        """(Un)write-protect physical pages
        (:func:`paged_cache.set_pages_ro`)."""
        self.cache = paged_cache.set_pages_ro(self.cache, pages, value)

    def set_pages_hot(self, pages, value: bool) -> None:
        """Set physical pages' residency bit
        (:func:`paged_cache.set_pages_hot`)."""
        self.cache = paged_cache.set_pages_hot(self.cache, pages, value)

    # -- tiered payload migration -------------------------------------------
    @property
    def page_payload_dtype(self):
        """Dtype of one page's payload
        (:func:`paged_cache.page_payload_dtype`)."""
        return paged_cache.page_payload_dtype(self.cache)

    @property
    def page_payload_elems(self) -> int:
        """Elements in one page's payload
        (:func:`paged_cache.page_payload_elems`)."""
        return paged_cache.page_payload_elems(self.cache)

    def gather_page_payloads(self, pages) -> Array:
        """Physical pages' payloads, ``(len(pages), page_payload_elems)``
        (:func:`paged_cache.gather_page_payloads`)."""
        return paged_cache.gather_page_payloads(self.cache, pages)

    def scatter_page_payloads(self, pages, payloads) -> None:
        """Write payloads back into physical pages
        (:func:`paged_cache.scatter_page_payloads`)."""
        self.cache = paged_cache.scatter_page_payloads(self.cache, pages,
                                                       payloads)

    def map_slot(self, slot: int, phys_pages, pos: int) -> None:
        """Point ``slot`` at ``phys_pages`` at position ``pos``
        (:func:`paged_cache.map_slot`)."""
        self.cache = paged_cache.map_slot(self.cache, slot, phys_pages, pos)

    def park(self, slot: int) -> None:
        """Point a released slot's table rows at the parking page
        (:func:`paged_cache.park_slot`): its idle decode writes must never
        land on pages a later admission owns."""
        self.cache = paged_cache.park_slot(self.cache, slot)


class ServeEngine:
    """Greedy-decoding continuous-batching engine over ``n_slots`` slots —
    the facade wiring scheduler, KV pool manager, and executor together."""

    def __init__(self, model, params, *, n_slots: int, max_seq: int,
                 enc_len: int = 0, paged_kv: bool = False,
                 page_tokens: int = 16, policy: str = "continuous",
                 prefix_share: bool = False,
                 kv_pages: int | tuple[int, int] | None = None,
                 tier_quantum: int = 2):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.paged_kv = paged_kv
        self.tiered = False
        if prefix_share and not paged_kv:
            raise ValueError("prefix_share=True requires paged_kv=True "
                             "(sharing happens on the physical page pool)")
        self.prefix_share = prefix_share
        self.executor = Executor(model, params, n_slots=n_slots,
                                 max_seq=max_seq, enc_len=enc_len,
                                 paged_kv=paged_kv, page_tokens=page_tokens)
        if paged_kv:
            self.page_tokens = page_tokens
            self.pages_per_slot = max_seq // page_tokens
            n_pages = n_slots * self.pages_per_slot
            host_pages = 0
            if isinstance(kv_pages, tuple):
                kv_pages, host_pages = kv_pages
                if host_pages < 0:
                    raise ValueError(
                        f"kv_pages=(hbm, host): host pages must be >= 0, "
                        f"got {host_pages}")
            if kv_pages is not None:
                if not self.pages_per_slot <= kv_pages <= n_pages:
                    raise ValueError(
                        f"kv_pages={kv_pages} must be between pages_per_slot"
                        f"={self.pages_per_slot} and the device pool size "
                        f"{n_pages}")
                n_pages = kv_pages
            self.pool = KVPoolManager(n_pages, host_pages)
            self.slot_pages: dict[int, list[int]] = {}
            self._ro_pages: set[int] = set()
            self.tiered = host_pages > 0
            self.tier_quantum = max(int(tier_quantum), 1)
            if self.tiered:
                if host_pages < self.pages_per_slot:
                    raise ValueError(
                        f"kv_pages=({n_pages}, {host_pages}): the host tier "
                        f"must hold at least one sequence "
                        f"(pages_per_slot={self.pages_per_slot})")
                self.tier = HostKVTier(host_pages,
                                       self.executor.page_payload_elems,
                                       self.executor.page_payload_dtype)
                self._cold: dict[int, dict] = {}   # slot -> {"host": [...]}
                self._active: set[int] = set()
                self._promote_next: list[int] = []
                self._hot_since: dict[int, int] = {}
        self.scheduler = Scheduler(n_slots, policy)
        self.slot_free = [True] * n_slots
        self._offline: set[int] = set()
        self.evictions = 0
        self.slot_req: dict[int, Request] = {}
        self.slot_generated: dict[int, list] = {}
        self.slot_pos: dict[int, int] = {}
        self.slot_entry: dict[int, object] = {}
        self.done: list[Completion] = []
        self._last_tokens = np.zeros((n_slots, 1), np.int32)
        self._tick = 0
        self._incomplete = 0
        self.max_live = 0
        # running sums over ticks of the pool's pages reserved and pages
        # holding tokens, as each tick starts
        self._tick_pages_reserved = 0
        self._tick_pages_used = 0
        self._await_out: list = []   # first token made this tick: t_out due
        # running sums of a counted model's expert counters, per phase
        self.moe_sums = ({f"{phase}_{k}": 0 for phase in ("prefill", "decode")
                          for k in ("moe_held", "experts_hit", "calls")}
                         if self.executor.counted else None)
        if self.moe_sums is not None:
            self.moe_sums["decode_live_tokens"] = 0

    # -- compat views ------------------------------------------------------------
    @property
    def cache(self):
        return self.executor.cache

    @property
    def pending(self) -> list[Request]:
        return [e.req for e in self.scheduler.pending_entries()]

    @property
    def allocator(self):
        """The pool layer (old name for the paged engine's allocator)."""
        return self.pool

    # -- public API --------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_seq:
            raise ValueError("prompt longer than max_seq")
        with _span("serve.submit", rid=req.rid):
            self.scheduler.submit(req, tick=self._tick,
                                  t_submit=time.perf_counter())

    def step(self) -> None:
        """One engine tick: migrate tiers, admit per the policy, then one
        decode step.  In tiered mode only **active** (HBM-resident) slots
        commit tokens — a cold slot's row is parked, its batched-decode
        output discarded, and its generation resumes bit-identically after
        promotion (greedy decode is row-independent)."""
        reserved = used = 0
        if self.paged_kv:
            reserved = self.pool.n_pages - self.pool.n_free
            used = self._pages_used()
            self._tick_pages_reserved += reserved
            self._tick_pages_used += used
        with _span("serve.step", tick=self._tick, live=len(self.slot_req),
                   queued=self.scheduler.pending_count,
                   pages_reserved=reserved, pages_used=used):
            self._step()
            now = time.perf_counter()
            for held in self._await_out:
                held.t_out = now
            self._await_out.clear()

    def _step(self) -> None:
        if self.paged_kv and self.tiered:
            self._tier_tick()
        with _span("serve.admit"):
            self._admit()
        if self.slot_req:
            if self.paged_kv and self.prefix_share:
                self._cow_tick()
            if self.paged_kv and self.tiered:
                # residency consult before decode: every active slot's pages
                # must be hot — a cold/in-flight page in a decode set means
                # host bookkeeping and device state disagree
                for slot in sorted(self._active):
                    self.pool.assert_resident(self.slot_pages[slot])
            span_args = {}
            if self.executor.counted:
                # the rows that commit a token, and the keys they attend:
                # each one's context, its new token in
                rows = [s for s in self.slot_req
                        if not self.tiered or s in self._active]
                span_args = {"rows": len(rows), "live_tokens": sum(
                    self.slot_pos[s] for s in rows)}
            nxt = self.executor.decode(self._last_tokens, **span_args)
            if self.executor.counted:
                self._count("decode", self.executor.last_counts,
                            span_args["live_tokens"])
            with _span("serve.commit"):
                for slot in list(self.slot_req):
                    if self.tiered and slot not in self._active:
                        continue
                    tok = int(nxt[slot])
                    self.slot_generated[slot].append(tok)
                    self.slot_pos[slot] += 1
                    self._last_tokens[slot, 0] = tok
                    self._finish_if_ended(slot)
        self._tick += 1

    def evict_slots(self, slots, *, requeue: bool = True) -> int:
        """Evict the live sequences on ``slots`` — the elastic path when a
        worker owning them is quarantined.

        Each victim releases its slot through the normal teardown (pages
        freed / parked, tier and COW bookkeeping run) and, under
        ``requeue=True``, its scheduler entry goes back to the **front** of
        the queue with its original arrival intact — re-admission
        re-prefills from the prompt, so greedy decode reproduces the lost
        tokens bit-identically and no request is silently dropped.
        Returns how many sequences were requeued."""
        n = 0
        for slot in slots:
            if slot not in self.slot_req:
                continue
            entry = self.slot_entry.get(slot)
            req = self.slot_req[slot]
            self._release(slot)
            self.evictions += 1
            if requeue:
                if entry is not None:
                    self.scheduler.requeue(entry)
                else:
                    self.scheduler.submit(req, tick=self._tick)
                n += 1
        return n

    def set_slots_offline(self, slots, offline: bool = True) -> None:
        """Take decode slots out of (or back into) the admission pool — an
        evicted worker's slots must not take new work, and a rejoined
        worker's come back.  Offline slots read as not-free, so every
        admission path (``_admit``, ticket windows via the free count)
        skips them without special-casing."""
        for slot in slots:
            if offline:
                if slot in self.slot_req:
                    raise ValueError(
                        f"slot {slot} still holds a live sequence — "
                        f"evict_slots() it before taking it offline")
                self._offline.add(slot)
                self.slot_free[slot] = False
            else:
                self._offline.discard(slot)
                if slot not in self.slot_req:
                    self.slot_free[slot] = True

    def run(self, max_ticks: int = 10_000, *,
            strict: bool = False) -> list[Completion]:
        """Drive ticks until every submitted request completes or
        ``max_ticks`` is exhausted.

        On exhaustion the still-in-flight work is **not** silently dropped:
        each live slot yields a ``Completion(finished=False)`` with its
        partial tokens, each still-queued request one with no tokens, and
        ``stats()['incomplete']`` counts them — or, under ``strict=True``,
        a ``RuntimeError`` names the unfinished rids.  Engine state is left
        intact either way, so ``run()`` can be called again to continue."""
        ticks = 0
        while ((self.scheduler.pending_count or self.slot_req)
               and ticks < max_ticks):
            self.step()
            ticks += 1
        live = [(slot, self.slot_req[slot]) for slot in sorted(self.slot_req)]
        queued = self.scheduler.pending_entries()
        self._incomplete = len(live) + len(queued)
        if self._incomplete and strict:
            rids = [r.rid for _, r in live] + [e.req.rid for e in queued]
            raise RuntimeError(
                f"run(max_ticks={max_ticks}) exhausted with "
                f"{self._incomplete} request(s) unfinished (rids {rids}) — "
                "raise max_ticks, or strict=False for explicit incomplete "
                "completions")
        out = list(self.done)
        for slot, req in live:
            out.append(self._completion(req, list(self.slot_generated[slot]),
                                        False, self.slot_entry.get(slot)))
        for e in queued:
            out.append(self._completion(e.req, [], False, e))
        return out

    def stats(self) -> dict:
        """Engine health across all three layers."""
        out = {"completed": len(self.done),
               "pending": self.scheduler.pending_count,
               "live_slots": len(self.slot_req), "paged_kv": self.paged_kv,
               "policy": self.scheduler.policy,
               "submitted": self.scheduler.submitted,
               "admitted": self.scheduler.admitted,
               "ticks": self._tick, "incomplete": self._incomplete,
               "max_live": self.max_live, "evictions": self.evictions,
               "offline_slots": len(self._offline),
               "prefill_shapes": len(self.executor.prefill_shapes)}
        if self.paged_kv:
            out.update(pages_allocated=self.pool.allocs,
                       pages_freed=self.pool.frees,
                       pages_free=self.pool.n_free,
                       page_tokens=self.page_tokens,
                       pages_shared=self.pool.shared_maps,
                       cow_copies=self.pool.cow_copies,
                       cow_debt=self.pool.cow_debt,
                       pages_used=self._pages_used(),
                       tick_pages_reserved=self._tick_pages_reserved,
                       tick_pages_used=self._tick_pages_used,
                       pool_bytes=self.executor.pool_bytes,
                       decode_pool_aliased_bytes=(
                           self.executor.decode_pool_aliased_bytes))
            if self.tiered:
                out.update(host_pages=self.pool.host.capacity,
                           host_pages_free=self.pool.host.n_free,
                           cold_slots=len(self._cold),
                           active_slots=len(self._active),
                           demotions=self.pool.demotions,
                           promotions=self.pool.promotions,
                           tier_stale_drops=int(self.tier.err_count))
        if self.moe_sums is not None:
            out.update(self.moe_sums)
        return out

    # -- internals --------------------------------------------------------------
    def _count(self, phase: str, counts, live_tokens: int = 0) -> tuple[int, int]:
        """Add one call's expert counters to the running sums."""
        held, hit = int(counts[0]), int(counts[1])
        sums = self.moe_sums
        sums[f"{phase}_moe_held"] += held
        sums[f"{phase}_experts_hit"] += hit
        sums[f"{phase}_calls"] += 1
        if phase == "decode":
            sums["decode_live_tokens"] += live_tokens
        return held, hit

    def _completion(self, req: Request, tokens: list, finished: bool,
                    entry) -> Completion:
        if entry is None:
            return Completion(req.rid, tokens, finished, 0, self._tick)
        return Completion(req.rid, tokens, finished, entry.arrival,
                          self._tick, entry.t_submit, entry.t_admit,
                          entry.t_first, entry.t_out)

    def _pages_used(self) -> int:
        """Pages of the HBM pool that hold tokens: a hot slot at ``pos``
        holds ``pos - 1`` tokens in its first pages; a page that slots
        share counts once, and cold slots hold no HBM pages."""
        pt = self.page_tokens
        held = [(pages, -(-(self.slot_pos[s] - 1) // pt))
                for s, pages in self.slot_pages.items()]
        if not self.prefix_share:
            return sum(n for _, n in held)
        return len({p for pages, n in held for p in pages[:n]})

    def _finish_if_ended(self, slot: int) -> bool:
        """Complete-and-release ``slot`` iff its latest token terminates the
        request (EOS, token budget, or cache full) — the single termination
        predicate shared by the decode loop and admission-time prefill."""
        req = self.slot_req[slot]
        gen = self.slot_generated[slot]
        ended = (gen[-1] == req.eos_id or
                 len(gen) >= req.max_new_tokens or
                 self.slot_pos[slot] >= self.max_seq - 1)
        if ended:
            c = self._completion(req, gen, True, self.slot_entry.get(slot))
            if c.t_first and not c.t_out:    # its first token is this tick's
                self._await_out.append(c)
            self.done.append(c)
            self._release(slot)
        return ended

    def _admit(self) -> None:
        """Admit what the scheduler selects, until it selects nothing (an
        admission-time completion frees its slot within the tick, so the
        loop re-asks — preserving the old engine's immediate reuse)."""
        while True:
            n_free = sum(self.slot_free)
            if self.paged_kv and self.tiered:
                # total-footprint pricing against the whole hierarchy: a
                # sequence may be admitted onto capacity that is partly
                # host-side (it will rotate through the cold tier), but
                # never onto capacity that does not exist — that is what
                # keeps admitted-but-cold sequences waiting their turn
                # instead of deadlocking the hot free list
                n_free = min(n_free, self.scheduler.price_admission(
                    pages_per_seq=self.pages_per_slot,
                    hbm_free=self.pool.n_free,
                    host_free=self.pool.host.n_free,
                    reserve=self.pool.cow_debt))
            entries = self.scheduler.select(n_free, live=len(self.slot_req),
                                            tick=self._tick)
            if not entries:
                return
            for idx, entry in enumerate(entries):
                slot = self.slot_free.index(True)
                if not self._admit_one(entry, slot):
                    # pool pressure: hand this and the rest back, front of
                    # queue, original order — retry next tick
                    for e in reversed(entries[idx:]):
                        self.scheduler.requeue(e)
                    return

    def _admit_one(self, entry, slot: int) -> bool:
        """Prefill one selected request into ``slot``.  Returns False (no
        state changed, entry must be requeued) when the pool cannot back it
        fork-safely."""
        req = entry.req
        if self.paged_kv:
            shared, shared_rw = ([], [])
            if self.prefix_share:
                shared, shared_rw = self._share_plan(req)
            n_fresh = self.pages_per_slot - len(shared) - len(shared_rw)
            # price shares by their true fork-debt delta: a writable share
            # of a page with read-only holders (or an RO share of a
            # writable-shared page) costs more than its share count
            debt = (self.pool.share_price(shared)
                    + self.pool.share_price(shared_rw, writable=True))
            if not self.pool.can_admit(n_fresh, debt):
                return False
            fresh = self.pool.alloc(n_fresh)
            if shared:
                self.pool.share_pages(shared)
            if shared_rw:
                self.pool.share_pages(shared_rw, writable=True)
            phys = shared + shared_rw + fresh
            self.slot_pages[slot] = phys
            write_ok = np.ones(self.pages_per_slot, bool)
            write_ok[:len(shared) + len(shared_rw)] = False
            newly_ro = [p for p in shared + shared_rw
                        if self.pool.refcount_of(p) >= 2]
            if newly_ro:
                self.executor.set_pages_ro(newly_ro, True)
                self._ro_pages.update(newly_ro)
            if self.tiered:
                if fresh:
                    self.executor.set_pages_hot(fresh, True)
                self._active.add(slot)
                self._hot_since[slot] = self._tick
            phys_arg = jnp.asarray(phys, jnp.int32)
            ok_arg = jnp.asarray(write_ok)
        else:
            phys_arg = jnp.zeros((0,), jnp.int32)
            ok_arg = jnp.zeros((0,), bool)
        span = _span("serve.prefill", rid=req.rid, prompt_len=len(req.prompt),
                     slot=slot)
        with span:
            entry.t_admit, entry.t_out = time.perf_counter(), 0.0
            tokens = jnp.asarray(req.prompt, jnp.int32)[None]
            first = self.executor.prefill(tokens, slot, phys_arg, ok_arg)
            if self.executor.counted:
                held, hit = self._count("prefill", self.executor.last_counts)
                if span is not _NO_SPAN:
                    span.set_metadata(moe_held=held, experts_hit=hit)
        entry.t_first = time.perf_counter()
        self._await_out.append(entry)
        self.slot_free[slot] = False
        self.slot_req[slot] = req
        self.slot_generated[slot] = [first]
        self.slot_pos[slot] = len(req.prompt) + 1
        self.slot_entry[slot] = entry
        self.max_live = max(self.max_live, len(self.slot_req))
        # the prefill token can already terminate the request (EOS, or
        # max_new_tokens=1, or the cache is full): complete-and-release
        # here, or the slot decodes a spurious extra step — and in paged
        # mode holds its KV pages — for a full extra tick
        if self._finish_if_ended(slot):
            return True
        self._last_tokens[slot, 0] = first
        return True

    def _share_plan(self, req: Request) -> tuple[list[int], list[int]]:
        """Find the live donor with the longest common prompt prefix and
        split its pages into (immutably shared, writable/COW shared).

        Full pages entirely inside the common prefix hold bit-identical KV
        for both sequences and are shared read-only.  The partial page at
        the prefix boundary is shared copy-on-write only when the new
        prompt ends exactly at the prefix — otherwise the new prefill must
        write that page's tail, which would need a fork *at admission*;
        allocating fresh is simpler and equally correct."""
        prompt = [int(t) for t in req.prompt]
        best_c, donor = 0, None
        for slot, dreq in self.slot_req.items():
            if slot not in self.slot_pages:
                continue
            dp = dreq.prompt
            c = 0
            for a, b in zip(prompt, dp):
                if a != int(b):
                    break
                c += 1
            if c > best_c:
                best_c, donor = c, slot
        if donor is None:
            return [], []
        pt = self.page_tokens
        n_full = min(best_c // pt, self.pages_per_slot)
        shared = [self.slot_pages[donor][j] for j in range(n_full)]
        shared_rw = []
        if (best_c % pt and len(prompt) == best_c
                and n_full < self.pages_per_slot):
            shared_rw = [self.slot_pages[donor][n_full]]
        return shared, shared_rw

    def _cow_tick(self) -> None:
        """Fork any shared page a live slot is about to write.

        The write position this tick is ``slot_pos - 1`` (prefill leaves
        ``slot_pos`` one ahead of the cache position).  If its page is
        still mapped by another sequence, the pool moves this holder onto a
        fresh page and the executor copies contents + remaps the table —
        before the decode scatter, so no write ever lands on a shared
        page."""
        for slot in list(self.slot_req):
            pages = self.slot_pages.get(slot)
            if not pages:
                continue
            wpos = self.slot_pos[slot] - 1
            j = wpos // self.page_tokens
            if j >= self.pages_per_slot:
                continue               # cache full: the write is dropped
            p = pages[j]
            if self.pool.refcount_of(p) <= 1:
                if p in self._ro_pages:     # last co-holder is gone
                    self.executor.set_pages_ro([p], False)
                    self._ro_pages.discard(p)
                continue
            new, _ = self.pool.cow_write(p)
            self.executor.fork_page(slot, j, p, new)
            pages[j] = new
            if self.pool.refcount_of(p) <= 1 and p in self._ro_pages:
                self.executor.set_pages_ro([p], False)
                self._ro_pages.discard(p)

    def _tier_tick(self) -> None:
        """One tier-rotation step, run at the top of every tick.

        Promotions are **scheduled a tick ahead** (``_promote_next``, via
        :meth:`KVPoolManager.queue_promote`) and executed here as prefetch
        edges of a single :func:`~repro.serve.paged.tier_step_plan` replay
        together with this tick's demote puts — the planned overlap the
        plan's phase table proves.  The sequence:

        1. demote the oldest-hot victims until the HBM free list can back
           the scheduled promotions, one fresh admission (if any request is
           pending and the hierarchy has room), and the COW fork reserve —
           payload snapshot, host-slot alloc, planned puts, then release
           (COW refcounts drop normally: sharing dissolves on demotion);
        2. promote the scheduled slots that now fit: planned gets land in
           fresh hot pages, the page-table row and position counter are
           restored (:meth:`Executor.map_slot`), and the cold copy is
           retired through ``memhandle_release`` — the epoch bump that
           makes any straggler handle to it stale;
        3. recompute the active set and schedule the next promotions
           (oldest-cold first, every ``tier_quantum`` ticks or immediately
           when nothing is active)."""
        pool, ex, tier = self.pool, self.executor, self.tier
        pps = self.pages_per_slot
        # promotions scheduled last tick (slots may have finished meanwhile)
        enter = [s for s in self._promote_next if s in self._cold]
        self._promote_next = []
        # demotion headroom also covers one fresh admission this tick
        admit_head = 0
        if (self.scheduler.pending_count and any(self.slot_free)
                and self.scheduler.price_admission(
                    pages_per_seq=pps, hbm_free=pool.n_free,
                    host_free=pool.host.n_free,
                    reserve=pool.cow_debt) > 0):
            admit_head = pps
        target = pps * len(enter) + admit_head + pool.cow_debt
        projected = pool.n_free
        host_room = pool.host.n_free
        leave: list[int] = []
        hot_live = sorted(
            (s for s in self.slot_req
             if s in self._active and s in self.slot_pages),
            key=lambda s: self._hot_since.get(s, 0))
        for s in hot_live:
            if projected >= target or host_room < pps:
                break
            # only sole-owner pages actually return to the free list; a
            # shared page's co-holders keep it resident
            projected += sum(1 for p in self.slot_pages[s]
                             if pool.refcount_of(p) == 1)
            host_room -= pps
            leave.append(s)
        demote_pages: list[int] = []
        for s in leave:
            demote_pages.extend(self.slot_pages[s])
        payloads = (ex.gather_page_payloads(demote_pages)
                    if demote_pages else None)
        host_slots = pool.alloc_cold(len(demote_pages)) if demote_pages else []
        for hp, hs in zip(demote_pages, host_slots):
            pool.queue_demote(hp, hs)
        # which scheduled promotions fit after this demotion round
        avail = projected - admit_head - pool.cow_debt
        promote: list[int] = []
        for s in enter:
            if avail >= pps:
                promote.append(s)
                avail -= pps
            else:
                self._promote_next.append(s)     # stays queued (in-flight)
        promote_hosts = [h for s in promote for h in self._cold[s]["host"]]
        # one planned tier step: promote gets (prefetch edges, dedicated
        # stream) issued ahead of the demote puts, one completion epoch
        tier.alloc(host_slots)
        promoted = tier.step(promote_hosts, host_slots, payloads)
        # commit demotions: park, release (COW machinery runs normally),
        # clear residency bits on pages that actually freed
        cursor = 0
        for s in leave:
            pages = self.slot_pages.pop(s)
            ex.park(s)
            dropped = pool.release(pages)
            ro_clear = [p for p in dropped if p in self._ro_pages]
            if ro_clear:
                ex.set_pages_ro(ro_clear, False)
                self._ro_pages.difference_update(ro_clear)
            freed = [p for p in dropped if pool.refcount_of(p) == 0]
            if freed:
                ex.set_pages_hot(freed, False)
            self._cold[s] = {"host": host_slots[cursor:cursor + pps]}
            cursor += pps
            self._active.discard(s)
            self._hot_since.pop(s, None)
        pool.drain_demotes()
        # commit promotions: payloads land in fresh hot pages, identity
        # (table row + position) restored, cold copies retired (epoch bump)
        if promote:
            cursor = 0
            for s in promote:
                hs = self._cold.pop(s)["host"]
                fresh = pool.alloc(pps)
                ex.scatter_page_payloads(fresh,
                                         promoted[cursor:cursor + pps])
                ex.set_pages_hot(fresh, True)
                ex.map_slot(s, fresh, self.slot_pos[s] - 1)
                self.slot_pages[s] = fresh
                tier.free(hs)
                pool.drain_promotes(hs)
                pool.free_cold(hs)
                self._hot_since[s] = self._tick
                cursor += pps
        self._active = {s for s in self.slot_req if s in self.slot_pages}
        # schedule the next promotion round a tick ahead: oldest-cold
        # first, on the rotation quantum (or immediately if nothing is
        # active — cold slots must never wait on an empty machine)
        if self._cold and (self._tick % self.tier_quantum == 0
                           or not self._active):
            k = max(1, (pool.n_pages // max(pps, 1)) // 2)
            cand = [s for s in self._cold
                    if s not in self._promote_next][:k]
            if cand:
                self._promote_next.extend(cand)
                pool.queue_promote(
                    [h for s in cand for h in self._cold[s]["host"]])

    def _release(self, slot: int) -> None:
        self.slot_free[slot] = slot not in self._offline
        del self.slot_req[slot]
        del self.slot_generated[slot]
        del self.slot_pos[slot]
        self.slot_entry.pop(slot, None)
        if self.paged_kv and slot in self.slot_pages:
            pages = self.slot_pages.pop(slot)
            with _span("serve.release", slot=slot, pages=len(pages)):
                # park the row before its pages go back to the free list:
                # idle rows keep scattering per-step KV, and those writes
                # must never land on pages a later admission may own
                self.executor.park(slot)
                dropped = self.pool.release(pages)
                ro_clear = [p for p in dropped if p in self._ro_pages]
                if ro_clear:
                    self.executor.set_pages_ro(ro_clear, False)
                    self._ro_pages.difference_update(ro_clear)
        if self.paged_kv and self.tiered:
            self._active.discard(slot)
            self._hot_since.pop(slot, None)
            if slot in self._promote_next:
                self._promote_next.remove(slot)
            if slot in self._cold:
                # a cold slot released outright (e.g. cancelled): retire its
                # host copy — the epoch bump makes any straggler stale
                hs = self._cold.pop(slot)["host"]
                self.tier.free(hs)
                self.pool.free_cold(hs)


__all__ = ["ServeEngine", "Executor", "Request", "Completion"]
