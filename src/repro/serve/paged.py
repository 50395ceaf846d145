"""Paged KV-cache as a dynamic RMA window — the serving-side use of P5.

The serving engine's KV pool is the TPU analogue of the paper's dynamic
window: pages (fixed-size token blocks) are *attached* segments of a
process-local pool, allocated and freed as sequences come and go — exactly
the "communication requirements change over time" motivation of paper §4.

Access paths, mirroring the paper's measurement taxonomy:

* ``query``    — the page's registration (offset/epoch) is looked up
  remotely per access (dynamic window without handles; Fig. 3b),
* ``memhandle`` — page descriptors are exchanged once at allocation; decode-
  time accesses are direct RDMA with zero lookup overhead (P5).  A page's
  handle dies with ``free_page`` (epoch bump) — use-after-free is dropped
  and counted, never corrupts (the life-time guarantee).
* ``accumulate_page`` — in-place remote page updates (running KV stats,
  correction deltas, counters) through the op-specialized accumulate engine
  on a same-op dup'd view (paper §2.3 hints × P4), addressed via the page's
  memory handle.

A disaggregated prefill→decode deployment ships page handles instead of page
contents; ``benchmarks.put_latency`` quantifies the per-access win.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.rma import (
    DynamicWindow,
    WindowConfig,
    memhandle_create,
    memhandle_release,
    win_from_memhandle,
)

Array = jax.Array

from repro.core.rma.plan import register_plan_cache as _register_plan_cache

_TRANSFER_PLANS: dict[tuple, object] = _register_plan_cache(
    "kv_transfer", {})


def transfer_plan(pool_pages: int, pages: tuple, page_elems: int, dtype,
                  perm: tuple, stream: int = 0, *,
                  naive_flush: bool = False, topology=None,
                  backend: str = "rma"):
    """Build (or fetch from the build-once cache) the compiled page-push
    schedule: one :meth:`RmaPlan.put_handle` per page on the batch's ordered
    stream, one exit flush epoch — 2 phases per page (payload + handle
    header) + 2 for the epoch, never a per-page ack.

    ``topology``: the declared host factorization (see
    ``repro.core.rma.Topology``).  A push whose ``perm`` stays on one host
    (e.g. prefill and decode pools co-located) is classified into the
    shared-memory tier — same 2-phase pages, but the exit epoch drains
    nothing.  Part of the cache key: a pool re-created under a different
    factorization never replays the old schedule.

    ``backend``: lowering target for :meth:`RmaPlan.compile`.  Page pushes
    record no collective macro, so ``"auto"``/``"gspmd"`` resolve to the
    substrate schedule; ``"interpret"`` compiles and executes through
    :meth:`CompiledPlan.interpret` only when given ``regs=`` registration
    state (without it the handle path raises)."""
    from repro.core.rma.plan import RmaPlan
    from repro.core.rma.topology import topology_fingerprint

    if backend == "auto":
        backend = "rma"        # no macro to ever pick gspmd for
    dt = jnp.dtype(dtype)
    key = (pool_pages, tuple(pages), page_elems, dt.name, perm, stream,
           naive_flush, topology_fingerprint(topology), backend)
    if key in _TRANSFER_PLANS:
        return _TRANSFER_PLANS[key]
    plan = RmaPlan(f"transfer_pages[{len(pages)}]", topology=topology)
    plan.window("pool", scope="thread", order=True, max_streams=stream + 1,
                dtype=dt, exit_epoch=True)
    plan.bind("handles", (pool_pages, 4), jnp.int32)
    for i, page in enumerate(pages):
        plan.bind(f"kv{i}", (page_elems,), dt)
        plan.put_handle("pool", f"kv{i}",
                        lambda env, p=page: env["handles"][p], perm,
                        slot=page, stream=stream, shape=(page_elems,),
                        dtype=dt, label=f"page{page}")
    compiled = plan.compile(naive_flush=naive_flush, backend=backend)
    _TRANSFER_PLANS[key] = compiled
    return compiled


@dataclasses.dataclass(frozen=True)
class PageSpec:
    page_tokens: int          # tokens per page
    kv_heads: int
    head_dim: int
    n_pages: int              # pool capacity

    @property
    def page_elems(self) -> int:
        return self.page_tokens * self.kv_heads * self.head_dim * 2  # K and V


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVWindow:
    """Fixed-capacity page pool exposed as a dynamic window.

    ``window.buffer`` is the flat pool; page *p* occupies
    ``[p·page_elems, (p+1)·page_elems)``.  ``page_map`` (host side) tracks
    free pages; ``handles`` holds each live page's memory handle (what a
    remote decode engine would receive).

    ``err_count`` aggregates the P5 stale-handle drops observed across every
    handle-path transfer issued through this pool (put / get / accumulate /
    batched transfers) — the per-transfer ``MemhandleWindow`` counters would
    otherwise die with their throwaway view.  The disagg engine surfaces it
    in its serving stats; a non-zero value means a peer pushed (or read)
    through a freed page's handle.
    """

    window: DynamicWindow
    handles: Array            # (n_pages, 4) int32 — live pages' memhandles
    live: Array               # (n_pages,) bool
    spec: PageSpec
    err_count: Array = None   # () int32 — aggregated stale-handle violations

    def __post_init__(self):
        if self.err_count is None:
            self.err_count = jnp.zeros((), jnp.int32)

    def tree_flatten(self):
        return (self.window, self.handles, self.live, self.err_count), (self.spec,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0], children[3])

    def _replace(self, **kw) -> "PagedKVWindow":
        return dataclasses.replace(self, **kw)

    # -- construction ---------------------------------------------------------
    @classmethod
    def create(cls, spec: PageSpec, axis: str, axis_size: int,
               dtype=jnp.bfloat16, *, topology=None) -> "PagedKVWindow":
        pool = jnp.zeros((spec.n_pages * spec.page_elems,), dtype)
        win = DynamicWindow.create_dynamic(
            pool, axis, axis_size,
            WindowConfig(scope="thread", order=True, max_streams=4,
                         topology=topology),
            max_attach=spec.n_pages, am_slots=1, am_msg=1)
        return cls(
            window=win,
            handles=jnp.zeros((spec.n_pages, 4), jnp.int32),
            live=jnp.zeros((spec.n_pages,), bool),
            spec=spec,
        )

    # -- page lifecycle ---------------------------------------------------------
    def alloc_page(self, page: int) -> "PagedKVWindow":
        """Attach page ``page`` and create its memory handle (P5): local,
        no communication — the handle is what peers get.

        Allocating a page that is already live raises with the page id
        (symmetric with the :meth:`free_page` double-free guard): a second
        attach would re-register the slot and mint a handle at the *same*
        epoch as the outstanding ones, silently re-arming every stale copy.
        As with ``free_page``, the guard runs whenever liveness is concrete;
        under a trace the epoch machinery remains the backstop."""
        import jax.core

        s = self.spec
        live = self.live[page] if 0 <= page < s.n_pages else False
        if not isinstance(live, jax.core.Tracer) and bool(live):
            raise ValueError(
                f"alloc_page({page}): page is already allocated "
                f"(double alloc — free_page it before re-attaching)")
        win = self.window.attach(page, offset=page * s.page_elems,
                                 size=s.page_elems)
        mh = memhandle_create(win, page)
        return self._replace(window=win, handles=self.handles.at[page].set(mh),
                             live=self.live.at[page].set(True))

    def free_page(self, page: int) -> "PagedKVWindow":
        """Release through the substrate's consolidated lifetime machinery:
        ``memhandle_release`` invalidates the slot, bumps the traced epoch
        (stale remote writes are dropped and counted) and records the release
        in the dup family's flush queues, so statically-created handle
        windows for this page raise on use-after-free.

        Freeing a page that is not live (double free, or never allocated)
        raises with the page id: a second release would bump the epoch past
        the one outstanding handles were checked against and silently re-arm
        a dead slot.  The guard runs whenever liveness is concrete (eager
        host-side pool management); under a trace the liveness bit is a
        tracer and the epoch machinery remains the backstop."""
        import jax.core

        live = self.live[page] if 0 <= page < self.spec.n_pages else False
        if not isinstance(live, jax.core.Tracer) and not bool(live):
            raise ValueError(
                f"free_page({page}): page is not allocated "
                f"(double free, or never alloc_page'd)")
        win = memhandle_release(self.window, page)
        return self._replace(window=win, handles=self.handles.at[page].set(0),
                             live=self.live.at[page].set(False))

    # -- data paths ---------------------------------------------------------------
    def write_page_local(self, page: int, kv: Array) -> "PagedKVWindow":
        """Local fill (the prefill engine writing its own pool)."""
        s = self.spec
        buf = jax.lax.dynamic_update_slice_in_dim(
            self.window.buffer, kv.reshape(-1).astype(self.window.buffer.dtype),
            page * s.page_elems, axis=0)
        return self._replace(window=self.window._with(buffer=buf))

    def read_page(self, page: int) -> Array:
        s = self.spec
        flat = jax.lax.dynamic_slice_in_dim(
            self.window.buffer, page * s.page_elems, s.page_elems, axis=0)
        return flat.reshape(2, s.page_tokens, s.kv_heads, s.head_dim)

    def put_page_remote(self, page: int, kv: Array, perm,
                        stream: int = 0, *, order: bool = True,
                        ) -> "PagedKVWindow":
        """Disaggregated path: push a filled page into a peer's pool through
        its memory handle — one RDMA phase, no target involvement.

        The transfer runs on a **duplicated view** of the pool window (paper
        P4) carrying the per-transfer config (ordered channel, thread-scope
        completion) — same backing pool, same flush queues, zero copies —
        instead of re-allocating or disturbing the pool's own config."""
        xfer = self.window.dup_with_info(order=order, scope="thread")
        mhwin = win_from_memhandle(xfer, self.handles[page], slot=page)
        mhwin = mhwin.put(kv.reshape(-1), perm, stream=stream)
        mhwin = mhwin.flush(stream)
        parent = dataclasses.replace(mhwin.parent, config=self.window.config)
        return self._replace(window=parent,
                             err_count=self.err_count + mhwin.err_count)

    def accumulate_page(self, page: int, update: Array, perm, *,
                        op: str = "sum", offset: int = 0, stream: int = 0,
                        ) -> "PagedKVWindow":
        """In-place remote update of a live page — running KV statistics,
        speculative-decode correction deltas, visit counters — through the
        op-specialized accumulate engine.

        The update travels through a **dup'd view declaring single-op usage**
        (``same_op=op``, paper §2.3 hints × P4 dup): small updates on atomic-
        capable dtypes lower to the 1-phase NIC-atomic path, large ones to
        the tiled VPU bandwidth path — never the conservative generic path a
        hint-less accumulate would take.  Addressing goes through the page's
        memory handle (P5), so the target is not involved in the lookup."""
        view = self.window.dup_with_info(order=True, scope="thread",
                                         same_op=op, accumulate_ops=(op,))
        mhwin = win_from_memhandle(view, self.handles[page], slot=page)
        mhwin = mhwin.accumulate(update.reshape(-1), perm, op=op,
                                 offset=offset, stream=stream)
        mhwin = mhwin.flush(stream)
        parent = dataclasses.replace(mhwin.parent, config=self.window.config)
        return self._replace(window=parent,
                             err_count=self.err_count + mhwin.err_count)

    def push_pages(self, pages, kvs, perm, stream: int = 0, *,
                   backend: str = "rma") -> "PagedKVWindow":
        """Batched disaggregated push as a **declarative-plan replay**: the
        batch's schedule — every page issued back-to-back through its memory
        handle on one ordered stream, one thread-scoped flush epoch for the
        whole batch, no per-page acks — is planned once per (pages, shape)
        signature and cached; each call replays it with this step's handles
        and payloads.  ``pages`` must be static (Python ints): the per-page
        registration slots are part of the plan, which is what arms the P5
        trace-time use-after-release check on every replay."""
        compiled = transfer_plan(
            self.spec.n_pages, tuple(pages), self.spec.page_elems,
            self.window.buffer.dtype, tuple(tuple(p) for p in perm), stream,
            topology=self.window.config.topology, backend=backend)
        bindings = {"handles": self.handles}
        for i, kv in enumerate(kvs):
            bindings[f"kv{i}"] = kv.reshape(-1).astype(self.window.buffer.dtype)
        res = compiled.execute({"pool": self.window}, bindings)
        return self._replace(window=res.windows["pool"],
                             err_count=self.err_count + res.err_count)

    def transfer_pages(self, pages, kvs, perm, stream: int = 0,
                       ) -> "PagedKVWindow":
        """Batched disaggregated push: every page is issued back-to-back on
        one dup'd ordered view and a **single** thread-scoped flush epoch
        completes the whole batch — the pipelined put+signal shape of the
        cross-pod exchange, applied to KV pages.  ``pages`` must be static
        (Python ints): the per-page handles are resolved at trace time.

        .. deprecated:: kept as a thin wrapper over the plan-native
           :meth:`push_pages` (same numerics, same phase structure); emits a
           ``DeprecationWarning`` once per process."""
        from repro.core.rma.plan import warn_legacy_once

        warn_legacy_once("PagedKVWindow.transfer_pages",
                         "PagedKVWindow.push_pages (plan replay)")
        return self.push_pages(pages, kvs, perm, stream=stream)

    def get_page_remote(self, page: int, perm, stream: int = 0,
                        ) -> tuple["PagedKVWindow", Array]:
        """Disaggregated read path: fetch a page from a peer's pool through
        its memory handle — one request/response RTT, no target lookup.

        Carries the P5 read guarantee end to end: a stale page handle's
        response comes back **zeroed** (never the reused memory) and the drop
        is aggregated into the pool's ``err_count`` — the decode engine can
        distinguish "page freed under me" from data."""
        s = self.spec
        xfer = self.window.dup_with_info(order=True, scope="thread")
        mhwin = win_from_memhandle(xfer, self.handles[page], slot=page)
        mhwin, flat = mhwin.get(perm, offset=0, size=s.page_elems,
                                stream=stream)
        mhwin = mhwin.flush(stream)
        parent = dataclasses.replace(mhwin.parent, config=self.window.config)
        pool = self._replace(window=parent,
                             err_count=self.err_count + mhwin.err_count)
        return pool, flat.reshape(2, s.page_tokens, s.kv_heads, s.head_dim)


# ---------------------------------------------------------------------------
# Host-side pool management: tier-generic refcounted core + the tiered manager
# ---------------------------------------------------------------------------

#: Residency states a physical page moves through in the tiered pool.
RESIDENT_HOT = "hot"            # device-resident, decodable
RESIDENT_COLD = "cold"          # host-resident (demoted), not decodable
RESIDENT_IN_FLIGHT = "in-flight"  # queued/under migration between tiers


class PageTier:
    """One memory tier's refcounted page core with copy-on-write sharing.

    This is the tier-generic half of the pool split: everything that makes
    "a page" safe to own — refcounts, the FIFO free list (freed pages are
    reused as late as possible, maximum grace for in-flight transfers),
    the COW ledger and fork-debt reserve, and the double-free / not-
    allocated guards — parameterized only by a name and a capacity.
    :class:`KVPoolManager` composes two of these (the HBM hot tier and the
    host-memory cold tier) and layers residency/migration state on top;
    neither tier knows the other exists.

    Guards: releasing a page with refcount 0 (double free / never
    allocated) raises with the page id; so does sharing or cow-writing one.
    :meth:`can_admit` reserves one free page per outstanding writable share
    (each such holder may still fork), so admission never promises pages a
    later COW fault will need.
    """

    def __init__(self, name: str, capacity: int):
        self.name = name
        self.capacity = capacity
        self._ref = [0] * capacity
        self._free = list(range(capacity))
        # writable-shared pages -> writer count (owner + writable sharers);
        # read-only sharers hold references but never fork
        self._cow: dict[int, int] = {}
        self.allocs = 0
        self.frees = 0
        self.cow_copies = 0
        self.shared_maps = 0

    # -- capacity ---------------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def cow_debt(self) -> int:
        """Free pages that must stay reserved for pending COW forks.

        Per writable-shared page the worst case is ``min(writers, ref - 1)``
        forks: every writer forks while other references pin the page, and
        the last writer writes in place only when no read-only holder
        remains (all-writable sharing keeps the classic ``ref - 1``)."""
        return sum(min(w, self._ref[p] - 1)
                   for p, w in self._cow.items() if self._ref[p] > 1)

    def can_admit(self, n_fresh: int, n_writable_shares: int = 0) -> bool:
        """Would allocating ``n_fresh`` pages plus ``n_writable_shares``
        more units of fork debt stay fork-safe?  Price shares with
        :meth:`share_price` — a writable share of a page that already has
        read-only holders costs *more* than one unit (the owner is dragged
        into forking too)."""
        return len(self._free) - self.cow_debt >= n_fresh + n_writable_shares

    def share_price(self, pages, *, writable: bool = False) -> int:
        """The COW-debt delta :meth:`share_pages` of ``pages`` would incur —
        what admission must pass to :meth:`can_admit`.  Non-writable shares
        are not free either: one more read-only holder of a writable-shared
        page can push its last writer from write-in-place to fork."""
        ref = {p: self._ref[p] for p in set(pages)}
        wrt = {p: self._cow.get(p) for p in set(pages)}

        def debt(p):
            w = wrt[p]
            return min(w, ref[p] - 1) if w is not None and ref[p] > 1 else 0

        delta = 0
        for p in pages:
            before = debt(p)
            ref[p] += 1
            if writable:
                wrt[p] = (wrt[p] if wrt[p] is not None else 1) + 1
            delta += debt(p) - before
        return delta

    # -- lifecycle ---------------------------------------------------------------
    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted ({self.name} tier): need {n} "
                f"pages, {len(self._free)}/{self.capacity} free")
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._ref[p] = 1
        self.allocs += n
        return pages

    def refcount_of(self, page: int) -> int:
        return self._ref[page]

    def share_pages(self, pages, *, writable: bool = False) -> None:
        """Map already-allocated pages into one more sequence (refcount+1).

        ``writable=True`` marks the share copy-on-write: the page sits at a
        holder's future write position (a partial prefix page) and one free
        page is reserved per extra holder for the eventual fork."""
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"share_pages({p}): page is not allocated")
            self._ref[p] += 1
            if writable:
                self._cow[p] = self._cow.get(p, 1) + 1
        self.shared_maps += len(pages)

    def cow_write(self, page: int) -> tuple[int, bool]:
        """Resolve a write to ``page``: ``(page, False)`` if this holder is
        the sole owner (write in place), else fork — allocate a fresh page,
        move one reference onto it, and return ``(new_page, True)``; the
        caller copies the contents and remaps its page table."""
        if self._ref[page] <= 0:
            raise ValueError(f"cow_write({page}): page is not allocated")
        if self._ref[page] == 1:
            self._cow.pop(page, None)
            return page, False
        if not self._free:
            raise RuntimeError(
                f"cow_write({page}): pool exhausted at fork "
                f"(admission outran the COW reserve)")
        new = self._free.pop(0)
        self._ref[new] = 1
        self._ref[page] -= 1
        if page in self._cow:
            self._cow[page] -= 1     # the forking writer moved off the page
            if self._cow[page] <= 0 or self._ref[page] <= 1:
                del self._cow[page]
        self.allocs += 1
        self.cow_copies += 1
        return new, True

    def release(self, pages) -> list[int]:
        """Drop one reference per page; pages reaching refcount 0 return to
        the FIFO free list.  Returns the pages whose refcount dropped to
        ``<= 1`` (no longer shared — the engine clears their write
        protection).  Raises on double free with the offending page id."""
        dropped = []
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(
                    f"release({p}): double free (page is not allocated)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
                self.frees += 1
                self._cow.pop(p, None)
                dropped.append(p)
            elif self._ref[p] == 1:
                self._cow.pop(p, None)
                dropped.append(p)
        return dropped

    def check_conservation(self) -> None:
        """Assert the tier's conservation invariants (the Hypothesis sweep's
        oracle): every page is exactly one of free or refcounted — live
        count + free-list size == capacity, the free list holds no
        duplicates and no live page, refcounts are non-negative, and the COW
        fork debt never exceeds the free pages backing it."""
        live = sum(1 for r in self._ref if r > 0)
        assert live + len(self._free) == self.capacity, \
            f"{self.name}: {live} live + {len(self._free)} free " \
            f"!= {self.capacity} pages"
        assert len(set(self._free)) == len(self._free), \
            f"{self.name}: duplicate pages in the free list"
        assert all(self._ref[p] == 0 for p in self._free), \
            f"{self.name}: live page on the free list"
        assert all(r >= 0 for r in self._ref), \
            f"{self.name}: negative refcount"
        assert self.cow_debt <= len(self._free), \
            f"{self.name}: cow_debt {self.cow_debt} exceeds " \
            f"{len(self._free)} free pages"


class KVPoolManager:
    """Tiered physical-page pool: an HBM hot tier + a host-memory cold tier.

    The serving engine's pool layer (``docs/serving_disagg.md``): it hands
    out the physical pages of the cache's page pools
    (``models/paged_cache.py``) from a FIFO free list, and lets sequences
    with a common prompt prefix *map the same physical page* — a refcount
    per page, :meth:`share_pages` to map an allocated page into another
    sequence, and :meth:`cow_write` to fork a
    shared page the moment a holder needs to write it (vLLM-style COW on the
    paper's memhandle lifetime model: a physical page is a memhandle whose
    exposure outlives any one sequence, and the epoch machinery — not this
    bookkeeping — is what catches a stale access if the two ever disagree).

    With ``host_pages > 0`` the pool becomes a **memory hierarchy**
    ("MPI Windows on Storage" applied to KV): two :class:`PageTier` cores —
    ``hbm`` (what decode reads) and ``host`` (cold spill, backed by a
    host-memory :class:`PagedKVWindow` at the engine layer) — plus
    per-page residency state and demotion/promotion queues.  Page naming is
    tier-scoped: ``("hbm", p)`` and ``("host", s)`` are different physical
    pages; a migration copies payload between them and retires one side.
    The refcount/COW machinery lives entirely in the hot tier — sharing
    dissolves at demotion (the cold copy is private to its sequence) so a
    cold page has exactly one owner by construction.

    Every pre-tier entry point (``alloc``/``release``/``share_pages``/
    ``cow_write``/``can_admit``/counters/``stats()``) delegates to the hot
    tier unchanged — a ``KVPoolManager(n)`` without host pages is
    behaviorally identical to the pre-hierarchy flat pool, FIFO order and
    error messages included.
    """

    def __init__(self, n_pages: int, host_pages: int = 0):
        self.hbm = PageTier("hbm", n_pages)
        self.host = PageTier("host", host_pages)
        #: residency by (tier_name, page): RESIDENT_* or absent (free)
        self._residency: dict[tuple[str, int], str] = {}
        self._demote_q: list[tuple[int, int]] = []   # (hbm_page, host_slot)
        self._promote_q: list[int] = []              # host_slot
        self.demotions = 0
        self.promotions = 0

    # -- hot-tier delegation (the pre-tier surface, byte-identical) ----------
    @property
    def n_pages(self) -> int:
        return self.hbm.capacity

    @property
    def n_free(self) -> int:
        return self.hbm.n_free

    @property
    def cow_debt(self) -> int:
        return self.hbm.cow_debt

    @property
    def allocs(self) -> int:
        return self.hbm.allocs

    @property
    def frees(self) -> int:
        return self.hbm.frees

    @property
    def cow_copies(self) -> int:
        return self.hbm.cow_copies

    @property
    def shared_maps(self) -> int:
        return self.hbm.shared_maps

    @property
    def _ref(self):
        return self.hbm._ref

    @property
    def _free(self):
        return self.hbm._free

    @property
    def _cow(self):
        return self.hbm._cow

    def can_admit(self, n_fresh: int, n_writable_shares: int = 0) -> bool:
        """Decode-set admission: would the **hot tier alone** back
        ``n_fresh`` fresh pages plus ``n_writable_shares`` writable shares,
        fork-safe?  (Total-footprint pricing against HBM+host is the
        scheduler's :meth:`~repro.serve.scheduler.Scheduler.
        price_admission`; this is the per-tick decode-set half.)"""
        return self.hbm.can_admit(n_fresh, n_writable_shares)

    def share_price(self, pages, *, writable: bool = False) -> int:
        return self.hbm.share_price(pages, writable=writable)

    def alloc(self, n: int) -> list[int]:
        pages = self.hbm.alloc(n)
        for p in pages:
            self._residency[("hbm", p)] = RESIDENT_HOT
        return pages

    def refcount_of(self, page: int) -> int:
        return self.hbm.refcount_of(page)

    def share_pages(self, pages, *, writable: bool = False) -> None:
        self.hbm.share_pages(pages, writable=writable)

    def cow_write(self, page: int) -> tuple[int, bool]:
        new, forked = self.hbm.cow_write(page)
        if forked:
            self._residency[("hbm", new)] = RESIDENT_HOT
        return new, forked

    def release(self, pages) -> list[int]:
        dropped = self.hbm.release(pages)
        for p in dropped:
            if self.hbm.refcount_of(p) == 0:
                self._residency.pop(("hbm", p), None)
        return dropped

    # -- cold tier + residency -----------------------------------------------
    def alloc_cold(self, n: int) -> list[int]:
        """Take ``n`` host-tier slots for incoming demotions; they report
        in-flight until :meth:`drain_demotes` lands the payloads."""
        slots = self.host.alloc(n)
        for s in slots:
            self._residency[("host", s)] = RESIDENT_IN_FLIGHT
        return slots

    def free_cold(self, slots) -> None:
        """Retire cold copies (their sequence promoted back, or finished).
        The backing window's ``free_page`` epoch bump — not this
        bookkeeping — is what makes outstanding handles stale."""
        self.host.release(slots)
        gone = set(slots)
        self._promote_q = [s for s in self._promote_q if s not in gone]
        for s in slots:
            self._residency.pop(("host", s), None)

    def residency(self, tier: str, page: int) -> str | None:
        """RESIDENT_* for a live page of ``tier`` (``"hbm"``/``"host"``),
        ``None`` if the page is free/unknown."""
        return self._residency.get((tier, page))

    def queue_demote(self, hbm_page: int, host_slot: int) -> None:
        """Stage one page for demotion: both sides report in-flight until
        the planned put lands and :meth:`drain_demotes` commits."""
        self._residency[("hbm", hbm_page)] = RESIDENT_IN_FLIGHT
        self._residency[("host", host_slot)] = RESIDENT_IN_FLIGHT
        self._demote_q.append((hbm_page, host_slot))

    def drain_demotes(self) -> list[tuple[int, int]]:
        """Commit every staged demotion (the planned puts completed): cold
        copies become resident, the HBM side returns to ``hot`` for the
        caller to release.  Returns the drained (hbm_page, host_slot)
        pairs."""
        pairs, self._demote_q = self._demote_q, []
        for hp, hs in pairs:
            self._residency[("hbm", hp)] = RESIDENT_HOT
            self._residency[("host", hs)] = RESIDENT_COLD
        self.demotions += len(pairs)
        return pairs

    def queue_promote(self, host_slots) -> None:
        """Schedule cold copies for promotion next tick (they report
        in-flight — neither decodable nor reclaimable while queued)."""
        for s in host_slots:
            self._residency[("host", s)] = RESIDENT_IN_FLIGHT
            self._promote_q.append(s)

    def drain_promotes(self, host_slots=None) -> list[int]:
        """Commit promotions for ``host_slots`` (default: everything
        queued): drop them from the queue and count them.  The caller
        lands the payloads in fresh hot pages and then :meth:`free_cold`\\ s
        the slots; a slot left queued (promotion deferred) stays
        in-flight."""
        if host_slots is None:
            done, self._promote_q = self._promote_q, []
        else:
            done = [s for s in self._promote_q if s in set(host_slots)]
            self._promote_q = [s for s in self._promote_q
                               if s not in set(host_slots)]
        self.promotions += len(done)
        return done

    def assert_resident(self, pages) -> None:
        """Raise unless every hot-tier page is decode-ready (``hot``): the
        engine's pre-decode residency check — a cold or in-flight page in a
        decode set means host and device state disagree."""
        for p in pages:
            r = self._residency.get(("hbm", p))
            if r != RESIDENT_HOT:
                raise RuntimeError(
                    f"page {p} is not resident (residency={r!r}) — "
                    "decode would read a non-hot page")

    def check_conservation(self) -> None:
        """Both tiers' conservation invariants plus the residency map's:
        every residency entry names a live page of its tier."""
        self.hbm.check_conservation()
        self.host.check_conservation()
        for (tier, p), state in self._residency.items():
            t = self.hbm if tier == "hbm" else self.host
            assert t.refcount_of(p) > 0, \
                f"residency entry for free page ({tier}, {p}): {state}"

    # -- health ----------------------------------------------------------------
    def stats(self) -> dict:
        live = sum(1 for r in self.hbm._ref if r > 0)
        st = {
            "n_pages": self.n_pages,
            "n_free": self.n_free,
            "live_pages": live,
            "occupancy": live / max(self.n_pages, 1),
            "allocs": self.allocs,
            "frees": self.frees,
            "cow_copies": self.cow_copies,
            "shared_maps": self.shared_maps,
            "cow_debt": self.cow_debt,
        }
        if self.host.capacity:
            st.update({
                "host_pages": self.host.capacity,
                "host_free": self.host.n_free,
                "cold_pages": sum(1 for v in self._residency.values()
                                  if v == RESIDENT_COLD),
                "in_flight": sum(1 for v in self._residency.values()
                                 if v == RESIDENT_IN_FLIGHT),
                "demotions": self.demotions,
                "promotions": self.promotions,
            })
        return st


# ---------------------------------------------------------------------------
# The cold tier's window: host-memory pages behind the same P5 machinery
# ---------------------------------------------------------------------------

_TIER_PLANS: dict[tuple, object] = _register_plan_cache("kv_tier_step", {})


def tier_step_plan(pool_pages: int, promote: tuple, demote: tuple,
                   page_elems: int, dtype, perm: tuple = ((0, 0),), *,
                   backend: str = "rma"):
    """Build (or fetch from the build-once cache) one decode tick's tier
    traffic as a compiled plan: promote ``get_handle``\\ s first — **prefetch
    edges** on the window's dedicated last stream — then the demote
    ``put_handle``\\ s (the cold-bound pages written behind the previous
    tick's attention) on the migration stream, then the gather that consumes
    the promoted payloads.  The planner places each promote's completion
    epoch as a ``prefetch-wait`` immediately before the gather, so the
    phase table *shows* the overlap::

        prefetch:promote[s]...   (dedicated stream, issued first)
        demote[t]...             (migration stream — overlaps the reads)
        prefetch-wait[host/3]    (promotion completes only here,
                                  provably before the gather)

    Stale handles — a cold page freed after demotion — zero-mask + count at
    the target (P5), which is what the demote→free→stale-read tests drive
    through this exact plan.  Output ``"promoted"`` stacks the fetched
    payloads ``(len(promote), page_elems)``; omitted when nothing
    promotes."""
    from repro.core.rma.plan import RmaPlan

    if backend == "auto":
        backend = "rma"        # no macro to ever pick gspmd for
    dt = jnp.dtype(dtype)
    key = (pool_pages, tuple(promote), tuple(demote), page_elems, dt.name,
           tuple(tuple(p) for p in perm), backend)
    if key in _TIER_PLANS:
        return _TIER_PLANS[key]
    plan = RmaPlan(f"kv-tier-step[p{len(promote)} d{len(demote)}]")
    plan.window("host", scope="thread", order=True, max_streams=4,
                dtype=dt, exit_epoch=True)
    plan.bind("handles", (pool_pages, 4), jnp.int32)
    gets = []
    for s in promote:
        gets.append(plan.get_handle(
            "host", lambda env, p=s: env["handles"][p], tuple(perm), slot=s,
            size=page_elems, stream=3, label=f"promote[{s}]"))
    for i, s in enumerate(demote):
        plan.bind(f"cold{i}", (page_elems,), dt)
        plan.put_handle("host", f"cold{i}",
                        lambda env, p=s: env["handles"][p], tuple(perm),
                        slot=s, stream=2, shape=(page_elems,), dtype=dt,
                        label=f"demote[{s}]")
    if gets:
        gather = plan.compute(
            lambda env: jnp.stack([env[g] for g in gets]),
            reads=tuple(gets), label="attention-gather")
        for g in gets:
            plan.prefetch(g, gather)
        plan.output("promoted", gather)
    compiled = plan.compile(backend=backend)
    _TIER_PLANS[key] = compiled
    return compiled


class HostKVTier:
    """The cold tier's storage: a host-memory page pool behind the *same*
    dynamic-window + memhandle machinery as the device pools.

    Demoted pages live as attached slots of a :class:`PagedKVWindow`
    (the "MPI Windows on Storage" move: the window abstraction extended
    down the memory hierarchy), so the P5 lifetime story applies unchanged
    — :meth:`free` releases through ``memhandle_release``, bumping the slot
    epoch, and any later promote of that slot comes back **zeroed and
    counted**, never as reused bytes.

    The serving engine is one process, so tier traffic executes the
    compiled :func:`tier_step_plan` under ``vmap(axis_name=...)`` with a
    single rank and a self-permutation — the degenerate mesh.  Same
    substrate, same epoch bookkeeping, same stale-handle guarantees as a
    real multi-device deployment (``tests/mdev/kv_tier.py`` runs the same
    plans on an 8-device mesh).

    A "page" here is one sequence page's **full payload across every pool
    the model keeps** (all layers' K and V bytes concatenated —
    ``page_elems`` from ``Executor.page_payload_elems``), so one slot
    round-trips one logical KV page regardless of how many scan-stacked
    pools back it on device."""

    def __init__(self, n_pages: int, page_elems: int, dtype, *,
                 axis: str = "x"):
        if page_elems % 2:
            raise ValueError(f"page_elems must be even, got {page_elems}")
        self.axis = axis
        # PageSpec models elems as tokens*heads*dim*2; the host tier stores
        # opaque payload bytes, so fold everything into the token factor
        self.spec = PageSpec(page_tokens=page_elems // 2, kv_heads=1,
                             head_dim=1, n_pages=n_pages)
        self.pool = PagedKVWindow.create(self.spec, axis, 1, dtype)
        self.dtype = jnp.dtype(dtype)

    @property
    def err_count(self) -> Array:
        """Aggregated P5 stale-handle drops observed by tier traffic."""
        return self.pool.err_count

    def alloc(self, slots) -> None:
        """Attach host slots (fresh handles) for incoming demotions."""
        for s in slots:
            self.pool = self.pool.alloc_page(int(s))

    def free(self, slots) -> None:
        """Release host slots through ``memhandle_release``: the epoch bump
        is the guarantee that a demoted-then-freed page is never read."""
        for s in slots:
            self.pool = self.pool.free_page(int(s))

    def step(self, promote_slots, demote_slots, demote_payloads):
        """Run one planned tier step: promote reads (prefetch edges) +
        demote writes, one replay.  ``demote_payloads`` is
        ``(len(demote_slots), page_elems)``; returns the promoted payloads
        ``(len(promote_slots), page_elems)`` or ``None``."""
        promote_slots = tuple(int(s) for s in promote_slots)
        demote_slots = tuple(int(s) for s in demote_slots)
        if not promote_slots and not demote_slots:
            return None
        compiled = tier_step_plan(self.spec.n_pages, promote_slots,
                                  demote_slots, self.spec.page_elems,
                                  self.dtype)
        bindings = {"handles": self.pool.handles}
        for i in range(len(demote_slots)):
            bindings[f"cold{i}"] = jnp.asarray(
                demote_payloads[i]).reshape(-1).astype(self.dtype)
        stacked_win = jax.tree_util.tree_map(lambda x: x[None],
                                             self.pool.window)
        stacked_b = {k: v[None] for k, v in bindings.items()}

        if promote_slots:
            def run(win, binds):
                res = compiled.execute({"host": win}, binds)
                return res.windows["host"], res.outputs["promoted"], \
                    res.err_count
            win, out, errs = jax.vmap(run, axis_name=self.axis)(
                stacked_win, stacked_b)
            promoted = out[0]
        else:
            def run(win, binds):
                res = compiled.execute({"host": win}, binds)
                return res.windows["host"], res.err_count
            win, errs = jax.vmap(run, axis_name=self.axis)(
                stacked_win, stacked_b)
            promoted = None
        self.pool = self.pool._replace(
            window=jax.tree_util.tree_map(lambda x: x[0], win),
            err_count=self.pool.err_count + errs.reshape(()).astype(jnp.int32))
        return promoted


__all__ = [
    "PageSpec", "PagedKVWindow", "PageTier", "KVPoolManager", "HostKVTier",
    "transfer_plan", "tier_step_plan",
    "RESIDENT_HOT", "RESIDENT_COLD", "RESIDENT_IN_FLIGHT",
]
