"""The paged KV cache: its layout, and every operation on it.

A paged self-attention cache dict holds physical page pools -- GQA
``{k_pages, v_pages}`` or MLA's latent ``{ckv_pages, kr_pages}`` -- beside
one ``page_table`` (each row's physical page per logical block), the
per-page bits ``page_ro`` (write protection) and ``page_hot`` (residency),
and ``pos``.  A prefix layer's dict holds these leaves as they are; a
scanned stack's dict carries a leading layers dim on every leaf.  The last
page of each pool is the **parking page**: no allocation ever owns it, so
the writes of idle rows and every dropped write land there.

This module is the one place that knows the layout.  The model reads and
writes the pools through it inside the jitted decode (:func:`paged_write`,
:func:`split_pools`, :func:`join_pools`); the serving executor
(``serve/engine.py``) builds the cache (:func:`paginate_cache`), fills a
slot's pages at admission (:func:`insert_prefill`), and rewrites the cache
between calls with the host-side functions of ``(cache, ...) -> cache``
at the end of this file.  It is the layout a decode worker's page pool
has in a disaggregated prefill→decode deployment
(``docs/serving_disagg.md``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

#: each dense cache kind's leaves and the page pools ``paginate_cache``
#: turns them into: GQA keys and values, and MLA's latent pair
PAGED_LEAVES = {
    frozenset({"k", "v", "pos"}): {"k": "k_pages", "v": "v_pages"},
    frozenset({"c_kv", "k_rope", "pos"}): {"c_kv": "ckv_pages",
                                           "k_rope": "kr_pages"},
}

#: lanes of a TPU vector register, the minor tile of its memory layout
LANES = 128


def page_shape(page_tokens: int, feature: tuple) -> tuple:
    """The shape of one page of a pool of ``feature``-shaped tokens:
    ``(page_tokens, *feature)``, or, for a 1-D feature that divides the
    lanes (MLA's 64-wide rope key), rows of ``LANES`` that each hold
    several tokens side by side.  A TPU lays a ``(…, pages, pt, 64)`` pool
    out with its pages minor, so every paged write or gather would first
    copy the whole pool into another layout; rows of ``LANES`` keep each
    page whole in its own tiles."""
    if (len(feature) == 1 and LANES % feature[0] == 0
            and page_tokens * feature[0] % LANES == 0):
        return (page_tokens * feature[0] // LANES, LANES)
    return (page_tokens, *feature)


def is_paged(d) -> bool:
    """Whether ``d`` is a paged self-attention cache (either kind)."""
    return isinstance(d, dict) and "page_table" in d


def page_pools(d) -> dict:
    """``{dense leaf: page pool}`` of a paged cache dict, in pool order."""
    for leaves in PAGED_LEAVES.values():
        if all(pool in d for pool in leaves.values()):
            return leaves
    raise ValueError(f"no page pools in a cache dict with keys {sorted(d)}")


def page_axis(d) -> int:
    """The axis of a paged dict's pools that indexes pages: 0, or 1 under
    the leading (layers) dim of a scanned stack."""
    return d["page_table"].ndim - 2


def parking_page(d) -> int:
    """The id of a paged dict's parking page (the last page)."""
    return d["page_ro"].shape[-1] - 1


def _rewrite(tree, match, fn):
    """Rebuild a cache tree of dicts and lists with ``fn`` applied to
    every dict that ``match`` accepts (the walk does not descend into
    those); every other leaf is kept."""
    if isinstance(tree, dict):
        if match(tree):
            return fn(tree)
        return {key: _rewrite(val, match, fn) for key, val in tree.items()}
    if isinstance(tree, list):
        return [_rewrite(val, match, fn) for val in tree]
    return tree


def _map_paged(cache, fn):
    """``cache`` with ``fn`` applied to every paged dict."""
    return _rewrite(cache, is_paged, fn)


def pool_leaves(cache) -> list:
    """Every page pool of a cache tree, as ``(paged dict, pool name, page
    axis)``, in the fixed walk order that :func:`gather_page_payloads`
    and :func:`scatter_page_payloads` share.  Empty where nothing pages."""
    found = []
    _map_paged(cache, found.append)
    return [(d, pool, page_axis(d)) for d in found
            for pool in page_pools(d).values()]


def _set_row(d, slot, row, pos):
    """``d`` with ``slot``'s page-table row set to ``row`` and its
    position to ``pos``, in every layer of a scanned stack's dict."""
    at = (slice(None),) * page_axis(d) + (slot,)
    return dict(d, page_table=d["page_table"].at[at].set(row),
                pos=d["pos"].at[at].set(pos))


# ---------------------------------------------------------------------------
# Building the layout
# ---------------------------------------------------------------------------


def paginate_cache(cache, page_tokens: int):
    """Convert every dense self-attention leaf group of a stack cache -- GQA
    ``{k, v, pos}`` or MLA's latent ``{c_kv, k_rope, pos}`` -- into the
    pooled page layout: ``{k_pages, v_pages, ...}`` or ``{ckv_pages,
    kr_pages, ...}`` beside one ``page_table``, ``page_ro``, ``page_hot``
    and ``pos``.

    Dense leaves of shape ``(…, B, S, *feature)`` become physical pools
    of ``B·S/pt`` allocatable pages (:func:`page_shape`) **plus one
    parking page**; every page-table entry starts pointing at the parking
    page, and the serving engine's pool manager (which hands out ids
    ``0 … B·S/pt − 1``) wires rows to real pages at slot admission.  The
    parking page matters: idle and released decode rows still scatter
    their (discarded) per-step KV through the table, and parking those
    writes on a page no allocation can ever own is what keeps them from
    corrupting a live slot's pages.  Leaves that are not self-attention KV
    (cross-attention, SSM state, the step counter, MoE counters) pass
    through unchanged, so hybrid stacks page only what pages.  Both kinds
    share the table and the two bit leaves, so the engine's allocator,
    release, COW fork and tier migration are one code path for both.

    The ``page_ro`` leaf is the pool's per-page write protection: the
    engine sets it for pages mapped by more than one sequence (COW prefix
    sharing), and the decode scatter (:func:`paged_write`) drops writes
    routed at a protected page exactly like overflow writes.  The parking
    page is never protected.

    The ``page_hot`` leaf is the pool's per-page **residency** bit (the
    tiered engine clears it for pages demoted to the host tier): the paged
    gather reroutes table entries at a non-hot page to the parking page and
    the scatter drops writes at one — defense in depth mirroring
    ``page_ro``, so a residency-bookkeeping bug reads zeros instead of a
    reclaimed page's bytes.  Everything starts hot (an untier'd engine
    never clears it), and the parking page is always hot."""
    def paginate(cache):
        leaves = PAGED_LEAVES[frozenset(cache)]
        first = cache[next(iter(leaves))]
        lead = cache["pos"].ndim - 1       # a scanned stack's layers dim
        b, s = first.shape[lead], first.shape[lead + 1]
        if s % page_tokens:
            raise ValueError(f"max_seq={s} not divisible by "
                             f"page_tokens={page_tokens}")
        pages_per_row = s // page_tokens
        n_alloc = b * pages_per_row        # the allocator's page ids
        lead_shape = first.shape[:lead]

        def repage(x):
            page = page_shape(page_tokens, x.shape[lead + 2:])
            pool = x.reshape(*lead_shape, n_alloc, *page)
            park = jnp.zeros((*lead_shape, 1, *page), pool.dtype)
            return jnp.concatenate([pool, park], axis=lead)

        out = {pool: repage(cache[dense]) for dense, pool in leaves.items()}
        out.update(
            page_table=jnp.full((*lead_shape, b, pages_per_row), n_alloc,
                                jnp.int32),
            page_ro=jnp.zeros((*lead_shape, n_alloc + 1), bool),
            page_hot=jnp.ones((*lead_shape, n_alloc + 1), bool),
            pos=cache["pos"])
        return out

    return _rewrite(cache, lambda d: frozenset(d) in PAGED_LEAVES, paginate)


# ---------------------------------------------------------------------------
# Device side: inside the jitted decode and prefill
# ---------------------------------------------------------------------------


def _page_routes(cache: dict, cols: Array, n_pages: int, pt: int):
    """Where a paged cache's new tokens land and what its rows read.

    ``cols`` (B, S) are the new tokens' positions; the pool has ``n_pages``
    physical pages (the last is the parking page) of ``pt`` tokens.
    Returns ``(phys, in_page, gather_table)``: each new token's physical
    page and offset in it, and the page table the attention gather reads
    through.  A row at ``pos == max_seq`` has no page for the new token;
    its scatter goes to an out-of-range physical id so it is dropped -- the
    same silent OOB-write drop the dense layout gives.  The same rules hold
    for GQA ``{k_pages, v_pages}`` and MLA ``{ckv_pages, kr_pages}`` pools.
    """
    table = cache["page_table"]            # (B, pages_per_row) int32
    rows = jnp.arange(table.shape[0])[:, None]
    page_idx = cols // pt
    pages_per_row = table.shape[-1]
    valid = page_idx < pages_per_row
    phys = table[rows, jnp.minimum(page_idx, pages_per_row - 1)]
    phys = jnp.where(valid, phys, n_pages)  # (B, S) page ids
    # COW prefix sharing: a page mapped by >1 sequence is write-protected —
    # the pool manager forks before any legitimate write reaches one, so a
    # scatter aimed at it means host and device state disagree; drop it
    # like an overflow write rather than corrupt the co-holder.  Only the
    # scatter is rerouted — the attention gather still reads shared pages
    # through the table.
    ro = cache["page_ro"][jnp.minimum(phys, n_pages - 1)]
    phys = jnp.where(ro, n_pages, phys)
    # tiered residency: a non-hot page's bytes live in the host tier
    # (demoted) or are mid-migration — the engine never decodes such a
    # slot, so a table entry still aimed at one means residency bookkeeping
    # and device state disagree.  Drop scatters at it like overflow writes
    # and reroute the gather to the (all-zero, always-hot) parking page
    # rather than read a physical page the pool may have re-issued.
    hot = cache["page_hot"]
    phys = jnp.where(hot[jnp.minimum(phys, n_pages - 1)], phys, n_pages)
    gather_table = jnp.where(hot[table], table, n_pages - 1)
    return phys, cols % pt, gather_table


def paged_write(cache: dict, new: dict, cols: Array):
    """Scatter each pool's new tokens (``new``: pool name -> (B, S,
    *feature)) into each row's pages (:func:`_page_routes`) and gather every
    row's logical view of each pool through its page table.  Returns the
    new cache and the views, (B, pages·pt, *feature) each, in ``new``'s
    order.

    A page is ``pt`` rows of one token each, or, in a pool of a narrow
    feature, rows of several tokens side by side (:func:`page_shape`); a
    token's window starts at its row and its lane offset in that row.  A
    cache that carries a ``layer`` index holds its pools stacked over the
    scanned layers (:func:`join_pools`): the scatter and the gather index
    the stacked pool at that layer directly, so no layer's pool is sliced
    out or written back."""
    lead = (cache["layer"],) if "layer" in cache else ()
    k = len(lead)
    first = next(iter(new))
    pool = cache[first]
    pt = pool.shape[k + 1] * (pool.shape[-1] // new[first].shape[-1])
    phys, in_page, gather_table = _page_routes(cache, cols, pool.shape[k], pt)
    B = cols.shape[0]
    out, views = dict(cache), []
    for name, val in new.items():
        pool, val = cache[name], val.astype(cache[name].dtype)
        per_row = pool.shape[-1] // val.shape[-1]   # tokens side by side
        if per_row == 1:
            pool = pool.at[lead + (phys, in_page)].set(val)
        else:
            # element by element, from the token's lane offset in its row
            # (a window that starts inside the row is not a native scatter)
            f = val.shape[-1]
            lanes = (in_page % per_row * f)[..., None] + jnp.arange(f)
            pool = pool.at[lead + (phys[..., None], (in_page // per_row)[..., None],
                                   lanes)].set(val)
        out[name] = pool
        views.append(pool[lead + (gather_table,)].reshape(B, -1, *val.shape[2:]))
    return out, views


def split_pools(tree):
    """Split a scanned block cache into (the tree without its page pools,
    the pools): each paged dict's pool leaves (:func:`page_pools`) go to a
    tree of their own, and its ``layer`` index is dropped.  The pools tree
    is empty where nothing is paged.  The layer scan of the stack carries
    the pools, stacked over the layers, and scans the rest."""
    if not isinstance(tree, dict):
        return tree, {}
    if is_paged(tree):
        names = set(page_pools(tree).values())
        return ({k: v for k, v in tree.items() if k not in names | {"layer"}},
                {k: tree[k] for k in names})
    rest, pools = {}, {}
    for key, val in tree.items():
        rest[key], sub = split_pools(val)
        if sub:
            pools[key] = sub
    return rest, pools


def join_pools(tree, pools, layer=None):
    """The inverse of :func:`split_pools`: each paged dict gets its pools
    back, and with ``layer`` the index of its layer in them."""
    if is_paged(tree):
        return dict(tree, **pools) if layer is None else dict(tree, **pools, layer=layer)
    return {key: join_pools(val, pools[key], layer) if key in pools else val
            for key, val in tree.items()}


def insert_prefill(full, one, slot, phys_pages, write_ok):
    """Scatter a dense (1, S, *feature) prefill cache ``one`` -- GQA K and
    V, or the latent pair -- into the slot's physical pages of the paged
    dict ``full`` and point the slot's page-table row at them.  Pages with
    ``write_ok=False`` are *shared* — the donor already holds their prefix
    KV — so their scatter is routed to the parking page while the table
    still maps them."""
    dest = jnp.where(write_ok, phys_pages, parking_page(full))
    ax = page_axis(full)

    def repage_scatter(pool, dense):
        # page by page, each an in-place dynamic_update_slice of the page
        # in every layer: a scatter of whole pages made the compiler copy
        # the pool into another tiling and back, and a scatter of rows ran
        # 5-7x slower than this loop (TPU v5e: 18.8 against 2.7 ms for
        # StarCoder2-3B's pools, 31.3 against 5.7 ms for DeepSeek-V2-Lite's)
        pages = dense.reshape(*dense.shape[:ax], -1, *pool.shape[ax + 1:])
        pages = pages.astype(pool.dtype)

        def put(j, pool):
            page = jax.lax.dynamic_slice_in_dim(pages, j, 1, axis=ax)
            start = (0,) * ax + (dest[j],) + (0,) * (pool.ndim - ax - 1)
            return jax.lax.dynamic_update_slice(pool, page, start)

        return jax.lax.fori_loop(0, pages.shape[ax], put, pool)

    out = _set_row(full, slot, phys_pages, one["pos"][..., 0])
    for dense, pool in page_pools(full).items():
        out[pool] = repage_scatter(full[pool], one[dense])
    return out


# ---------------------------------------------------------------------------
# Host side: rewrites of the whole cache between the jitted calls
# ---------------------------------------------------------------------------


def fork_page(cache, slot: int, j: int, src: int, dst: int):
    """Copy-on-write fork: copy physical page ``src`` → ``dst`` in every
    pool and point ``slot``'s table entry ``j`` at the copy, a writable,
    resident page."""
    def fork(d):
        lead = (slice(None),) * page_axis(d)
        out = dict(d)
        for pool in page_pools(d).values():
            leaf = d[pool]
            out[pool] = leaf.at[lead + (dst,)].set(leaf[lead + (src,)])
        out["page_table"] = d["page_table"].at[lead + (slot, j)].set(dst)
        out["page_ro"] = d["page_ro"].at[..., dst].set(False)
        out["page_hot"] = d["page_hot"].at[..., dst].set(True)
        return out

    return _map_paged(cache, fork)


def set_pages_ro(cache, pages, value: bool):
    """(Un)write-protect physical pages: decode scatters at an RO page are
    dropped like overflow writes (defense in depth — the pool manager
    forks before any legitimate write reaches one)."""
    idx = jnp.asarray(list(pages), jnp.int32)
    return _map_paged(cache, lambda d: dict(
        d, page_ro=d["page_ro"].at[..., idx].set(value)))


def set_pages_hot(cache, pages, value: bool):
    """Set physical pages' residency bit.  The tiered engine clears it
    when a page's bytes leave for the host tier and sets it when fresh
    pages are wired (admission, promotion, COW fork); :func:`paged_write`
    reroutes any gather or scatter still aimed at a non-hot page to the
    parking page — defense in depth mirroring ``page_ro``."""
    idx = jnp.asarray(list(pages), jnp.int32)
    return _map_paged(cache, lambda d: dict(
        d, page_hot=d["page_hot"].at[..., idx].set(value)))


def page_payload_dtype(cache):
    """Dtype of the concatenated per-page payload (the pools' dtype)."""
    for d, pool, _ in pool_leaves(cache):
        return d[pool].dtype
    raise ValueError("no paged pools in this cache")


def page_payload_elems(cache) -> int:
    """Elements in one page's full payload: every pool's elements for that
    page concatenated (K and V, or the latent pair; a scan-stacked pool
    contributes all its layers), so one host-tier slot round-trips one
    logical KV page no matter how the stack is laid out."""
    n = sum(d[pool].size // d[pool].shape[ax]
            for d, pool, ax in pool_leaves(cache))
    if not n:
        raise ValueError("no paged pools in this cache")
    return n


def gather_page_payloads(cache, pages) -> Array:
    """Read physical pages' full payloads — ``(len(pages),
    page_payload_elems)`` — in the fixed pool walk order
    :func:`scatter_page_payloads` writes them back in.  This is the
    demotion snapshot: because shared (refcount ≥ 2) pages are never
    written (the pool forks first), a slot's page list read here is
    exactly its logical KV state."""
    pages = list(pages)
    idx = jnp.asarray(pages, jnp.int32)
    dt = page_payload_dtype(cache)
    parts = [jnp.moveaxis(jnp.take(d[pool], idx, axis=ax), ax, 0)
             .reshape(len(pages), -1).astype(dt)
             for d, pool, ax in pool_leaves(cache)]
    return jnp.concatenate(parts, axis=1)


def scatter_page_payloads(cache, pages, payloads):
    """Write promoted payloads back into physical pages — the exact
    inverse of :func:`gather_page_payloads` (same walk order, per-leaf
    dtype restored), so a demote→promote round trip is bit-identical."""
    pages = list(pages)
    idx = jnp.asarray(pages, jnp.int32)
    payloads = jnp.asarray(payloads).reshape(len(pages), -1)
    cur = [0]

    def put(d):
        out = dict(d)
        ax = page_axis(d)
        for pool in page_pools(d).values():
            leaf = d[pool]
            shape = (len(pages),) + leaf.shape[:ax] + leaf.shape[ax + 1:]
            take = leaf.size // leaf.shape[ax]
            chunk = payloads[:, cur[0]:cur[0] + take].reshape(shape)
            out[pool] = leaf.at[(slice(None),) * ax + (idx,)].set(
                jnp.moveaxis(chunk.astype(leaf.dtype), 0, ax))
            cur[0] += take
        return out

    return _map_paged(cache, put)


def map_slot(cache, slot: int, phys_pages, pos: int):
    """Point ``slot``'s page-table rows at ``phys_pages`` and restore its
    cache position — how a promoted sequence gets its device identity
    back after its pages round-tripped through the host tier.

    Restores **both** position counters: the paged dicts' per-row ``pos``
    (scatter target + causal mask) and the stack's top-level ``step``
    counter (rope positions) — the latter kept advancing while the slot
    sat cold, since parked rows still ride the batched decode."""
    phys = jnp.asarray(list(phys_pages), jnp.int32)
    cache = _map_paged(cache, lambda d: _set_row(d, slot, phys, pos))
    return dict(cache, step=cache["step"].at[slot].set(pos))


def park_slot(cache, slot: int):
    """Point a released slot's page-table rows back at the parking page and
    rewind its position counter — after this, the slot's idle decode writes
    land on the parking page and its old (now freed, maybe re-allocated)
    pages are never touched again."""
    return _map_paged(cache, lambda d: _set_row(d, slot, parking_page(d), 0))


__all__ = [
    "PAGED_LEAVES", "LANES", "page_shape",
    "is_paged", "page_pools", "page_axis", "parking_page",
    "pool_leaves", "paginate_cache",
    "paged_write", "split_pools", "join_pools", "insert_prefill",
    "fork_page", "set_pages_ro", "set_pages_hot",
    "page_payload_dtype", "page_payload_elems",
    "gather_page_payloads", "scatter_page_payloads",
    "map_slot", "park_slot",
]
