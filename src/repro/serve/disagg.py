"""Disaggregated prefill→decode serving on the RMA substrate.

This module is the application-scale composition of the paper's proposals —
the serving data plane the ROADMAP asks for, built entirely out of the
primitives the RMA layer already demonstrates in isolation:

* **P5 (memory handles)** — decode engines expose their KV pool as a
  :class:`~repro.serve.paged.PagedKVWindow`; page descriptors are exchanged
  once at allocation and every prefill push is direct RDMA through the
  handle, zero lookup overhead (paper §4.2, Fig. 12).  The lifetime
  guarantee makes eviction safe: a push or read racing a ``free_page`` is
  dropped/zero-masked and *counted*, never corrupts reused memory.
* **P2 (ordered sequences)** — a sequence's pages are issued back-to-back on
  one ordered channel and the per-sequence **doorbell** (``put_signal``)
  chains behind the last page: one data phase per page, one flush epoch per
  batch, no per-page acks (paper Listing 2 at serving scale; foMPI's
  notified-access recipe).
* **P3 (op intrinsics)** — decode **admission** is a remote atomic: lanes
  claim slot tickets with ``fetch_op`` counters on a small control window
  (same_op="sum" declared, so the doorbell flag lowers to the 1-phase
  NIC-atomic path).
* **P1 × P4 (scoped flushes on dup'd views)** — every decode lane runs on
  its own issue stream of the shared substrate and completes with
  *thread-scoped* flush epochs, so lanes never serialize each other's
  completion; per-use configs ride zero-copy dup'd views of the one pool.

Layout of the control window (int32 words)::

    [ticket | meta(seq 0), bell(seq 0) | meta(seq 1), bell(seq 1) | ...]

``ticket`` is the fetch_op admission counter; per sequence, ``meta`` carries
the page count of the pushed sequence and ``bell`` is the doorbell flag the
consumer polls.

The SPMD functions here run inside ``shard_map`` (prefill devices push to
decode devices over a mesh axis).  The single-process
:class:`~repro.serve.engine.ServeEngine` (``paged_kv=True``) keeps its KV
cache in the same page-table discipline (``models/paged_cache.py``), so the
engine's cache *is* the decode-side pool layout a disaggregated deployment
would receive pushes into.

Run the 8-fake-device round-trip demo (prefill→push→doorbell→admission→
decode through the handle path) with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.serve.disagg
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.rma import (
    SCOPE_THREAD,
    Window,
    WindowConfig,
    put_signal,
)
from repro.serve.paged import PagedKVWindow, PageSpec
from repro.serve.scheduler import Scheduler

Array = jax.Array

#: Control-window word 0: the fetch_op admission ticket counter.
CTRL_TICKET = 0


def ctrl_meta_offset(seq: int) -> int:
    """Word carrying sequence ``seq``'s pushed page count."""
    return 1 + 2 * seq


def ctrl_flag_offset(seq: int) -> int:
    """Sequence ``seq``'s doorbell flag word."""
    return 2 + 2 * seq


def ctrl_size(n_seqs: int) -> int:
    return 1 + 2 * n_seqs


def make_control_window(n_seqs: int, axis: str, axis_size: int, *,
                        n_lanes: int = 2) -> Window:
    """The decode-side control window: ticket counter + per-sequence
    (meta, doorbell) word pairs.

    Declared ``same_op="sum"`` so doorbell flags route through the
    accumulate engine's 1-phase intrinsic path, ``order=True`` so a doorbell
    chains behind its sequence's data with no intermediate flush, and
    thread scope with one issue stream per decode lane (P1 × P4)."""
    buf = jnp.zeros((ctrl_size(n_seqs),), jnp.int32)
    cfg = WindowConfig(scope=SCOPE_THREAD, order=True, max_streams=n_lanes,
                      same_op="sum", accumulate_ops=("sum",))
    return Window.allocate(buf, axis, axis_size, cfg)


# ---------------------------------------------------------------------------
# SPMD data plane: push / doorbell / admission
# ---------------------------------------------------------------------------


def push_sequence(pool: PagedKVWindow, ctrl: Window, seq: int,
                  pages, kvs, perm, *, lane: int = 0,
                  ) -> tuple[PagedKVWindow, Window]:
    """Prefill side: push one sequence's filled pages into the decode pool
    and ring its doorbell.

    The pages ride a single batched :meth:`PagedKVWindow.push_pages` (a
    compiled-plan replay: one ordered view, one thread-scoped flush epoch
    for the whole batch); the doorbell is a ``put_signal`` on the control
    window — the
    page count lands in the sequence's meta word and the flag accumulate
    chains behind it on the same ordered channel.  The control window is a
    *different* substrate than the pool, so the doorbell is sequenced
    ``after=`` the pool lane's post-flush completion token: it cannot land
    before the batch's flush epoch completes — notified access, a consumer
    that observes ``bell ≠ 0`` may read the pages with no flush of its own.
    Everything is issued on ``lane``'s stream, so concurrent sequences on
    different lanes neither share a flush epoch nor serialize."""
    pool = pool.push_pages(pages, kvs, perm, stream=lane)
    ctrl = put_signal(ctrl, jnp.asarray([len(pages)], jnp.int32), perm,
                      data_offset=ctrl_meta_offset(seq),
                      flag_offset=ctrl_flag_offset(seq), stream=lane,
                      after=pool.window.completion_token(lane))
    return pool, ctrl


def claim_slot(ctrl: Window, perm, *, n_slots: int, lane: int = 0,
               ) -> tuple[Window, Array, Array]:
    """Decode admission: atomically claim the next ticket on the target's
    control window (``MPI_Fetch_and_op`` on the counter word) and map it to
    a decode slot.  Returns ``(ctrl, ticket, slot)``."""
    ctrl, old = ctrl.fetch_op(jnp.ones((1,), jnp.int32), perm, op="sum",
                              offset=CTRL_TICKET, stream=lane)
    ticket = old[0]
    return ctrl, ticket, jnp.mod(ticket, n_slots)


def claim_slots(ctrl: Window, perm, scheduler, *, live: int = 0,
                lane: int = 0, max_claims: int | None = None,
                source: str | None = None) -> tuple[Window, list, list]:
    """Policy-driven decode admission: claim up to the scheduler's ticket
    budget for this tick (:meth:`repro.serve.scheduler.Scheduler.
    ticket_window` — 0 under ``static`` policy while sequences are live,
    the free-slot count otherwise) via remote fetch_op, mapping each ticket
    through :meth:`~repro.serve.scheduler.Scheduler.slot_for_ticket`.

    ``source`` names the claiming worker: its claim count is registered
    host-side (:meth:`~repro.serve.scheduler.Scheduler.note_claims`) so
    the tickets count against later windows until the worker binds them to
    live sequences (``consume_claims``) — or is evicted, when
    ``release_claims`` returns them (the elastic path; a leaked claim
    would stall admission forever).  The ticket *values* stay device-side
    (they are tracers inside the SPMD region); only the count is tracked.

    Returns ``(ctrl, tickets, slots)`` — possibly empty lists when the
    policy grants no admissions."""
    budget = scheduler.ticket_window(live)
    if max_claims is not None:
        budget = min(budget, max_claims)
    tickets, slots = [], []
    for _ in range(budget):
        ctrl, old = ctrl.fetch_op(jnp.ones((1,), jnp.int32), perm, op="sum",
                                  offset=CTRL_TICKET, stream=lane)
        tickets.append(old[0])
        slots.append(scheduler.slot_for_ticket(old[0]))
    if source is not None:
        scheduler.note_claims(len(tickets), source=source)
    return ctrl, tickets, slots


def read_doorbell(ctrl: Window, seq: int) -> tuple[Array, Array]:
    """Consumer-side poll: ``(flag, page_count)`` for sequence ``seq`` —
    local reads of the control window, no communication."""
    return ctrl.buffer[ctrl_flag_offset(seq)], ctrl.buffer[ctrl_meta_offset(seq)]


def pool_stats(pool: PagedKVWindow) -> dict[str, Array]:
    """The disagg engine's pool-health stats, aggregated across every
    handle-path transfer: live page count and the P5 stale-handle drop
    counter (non-zero ⇒ a peer pushed or read through a freed page)."""
    return {
        "live_pages": pool.live.sum().astype(jnp.int32),
        "err_count": pool.err_count,
    }


# ---------------------------------------------------------------------------
# The round-trip demo (8 fake devices): prefill→push→doorbell→admit→decode
# ---------------------------------------------------------------------------

N_DEMO_DEV = 8


def demo_round_trip(n_seqs: int = 2, pages_per_seq: int = 2,
                    n_lanes: int = 2, verbose: bool = True,
                    policy: str = "continuous") -> dict:
    """Drive one full disaggregated round trip across a ring of devices.

    Every device plays both roles (SPMD): as a *prefill* worker it fills
    ``n_seqs`` sequences' pages and pushes them into its ring successor's
    pool through memory handles, ringing one doorbell per sequence; as a
    *decode* worker it receives pushes from its predecessor, claims
    admission tickets with remote fetch_op, reads the doorbells/meta words
    and decodes (reads) the pushed pages — plus one stale-handle read after
    an eviction to show the P5 read guarantee end to end."""
    from jax.sharding import PartitionSpec as P

    from repro import compat

    n = N_DEMO_DEV
    if len(jax.devices()) < n:
        raise SystemExit(f"demo needs {n} devices "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = compat.make_mesh((n,), ("x",))
    perm = [(i, (i + 1) % n) for i in range(n)]
    spec = PageSpec(page_tokens=4, kv_heads=2, head_dim=8,
                    n_pages=n_seqs * pages_per_seq + 1)

    def scenario(_):
        pool = PagedKVWindow.create(spec, "x", n, dtype=jnp.float32)
        ctrl = make_control_window(n_seqs, "x", n, n_lanes=n_lanes)
        # decode side: allocate + register the pages each sequence will land
        # in (this is the once-per-allocation handle exchange of P5)
        for p in range(n_seqs * pages_per_seq):
            pool = pool.alloc_page(p)
        # prefill side: fill pages locally, push each sequence on its lane
        for s in range(n_seqs):
            pages = [s * pages_per_seq + j for j in range(pages_per_seq)]
            kvs = [jnp.full((2, spec.page_tokens, spec.kv_heads, spec.head_dim),
                            1.0 + s + 0.25 * j, jnp.float32)
                   for j in range(pages_per_seq)]
            pool, ctrl = push_sequence(pool, ctrl, s, pages, kvs, perm,
                                       lane=s % n_lanes)
        for lane in range(min(n_lanes, n_seqs)):
            ctrl = ctrl.flush(stream=lane)        # thread-scoped: per lane
        # decode admission: the scheduler policy grants each lane's ticket
        # budget (claim_slots), claimed with remote atomics
        sched = Scheduler(n_seqs, policy)
        tickets = []
        for lane in range(n_lanes):
            ctrl, ts, _slots = claim_slots(ctrl, perm, sched, live=0,
                                           lane=lane, max_claims=1)
            ctrl = ctrl.flush(stream=lane)
            tickets.extend(ts)
        # decode: doorbells + page contents pushed by the ring predecessor
        bells = [read_doorbell(ctrl, s) for s in range(n_seqs)]
        vals = [pool.read_page(s * pages_per_seq)[0, 0, 0, 0]
                for s in range(n_seqs)]
        # eviction: free sequence 0's first page; a read through the old
        # handle must come back zero-masked and counted, never reused memory
        stale_handle = pool.handles[0]
        pool = pool.free_page(0)
        from repro.core.rma import win_from_memhandle
        mhw = win_from_memhandle(pool.window, stale_handle)
        mhw, stale = mhw.get(perm, offset=0, size=4)
        stats = pool_stats(pool)
        out = jnp.concatenate([
            jnp.stack(vals),
            jnp.stack([b[0] for b in bells]).astype(jnp.float32),
            jnp.stack([b[1] for b in bells]).astype(jnp.float32),
            jnp.stack(tickets).astype(jnp.float32),
            stale[:4].astype(jnp.float32),
            (stats["err_count"] + mhw.err_count)[None].astype(jnp.float32),
            stats["live_pages"][None].astype(jnp.float32),
        ])
        return out[None]

    g = jax.jit(compat.shard_map(scenario, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=False))
    import numpy as np
    out = np.asarray(g(jnp.zeros((n, 1))))
    k = n_seqs
    vals, bells, metas = out[:, :k], out[:, k:2 * k], out[:, 2 * k:3 * k]
    tickets = out[:, 3 * k:3 * k + n_lanes]
    stale = out[:, 3 * k + n_lanes:3 * k + n_lanes + 4]
    errs = out[:, 3 * k + n_lanes + 4]
    live = out[:, 3 * k + n_lanes + 5]
    checks = {
        "pages_landed": bool(np.allclose(vals, [1.0 + s for s in range(k)])),
        "doorbells": bool((bells == 1.0).all()),
        "meta_page_counts": bool((metas == pages_per_seq).all()),
        "tickets": bool((tickets == np.arange(n_lanes)).all()),
        "stale_read_masked": bool((stale == 0.0).all()),
        "stale_read_counted": bool((errs == 1.0).all()),
        "live_pages": bool((live == k * pages_per_seq - 1).all()),
    }
    if verbose:
        print(f"[disagg] {k} seqs x {pages_per_seq} pages pushed over "
              f"{n}-device ring on {n_lanes} lanes ({policy} admission)")
        for name, ok in checks.items():
            print(f"[disagg]   {name}: {'OK' if ok else 'FAIL'}")
    if not all(checks.values()):
        raise SystemExit(f"disagg round-trip failed: {checks}")
    return checks


if __name__ == "__main__":
    demo_round_trip()
    print("DISAGG ROUND-TRIP OK")


__all__ = [
    "CTRL_TICKET",
    "ctrl_meta_offset",
    "ctrl_flag_offset",
    "ctrl_size",
    "make_control_window",
    "push_sequence",
    "claim_slot",
    "claim_slots",
    "read_doorbell",
    "pool_stats",
    "demo_round_trip",
]
