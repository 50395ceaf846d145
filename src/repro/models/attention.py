"""Attention variants: GQA (+qk-norm, biases), MLA (DeepSeek), online-softmax
blockwise attention, and the KV-cache decode path.

Shapes (batch B, sequence S, query heads H, kv heads KV, head_dim hd):

* weights: wq (d, H, hd), wk/wv (d, KV, hd), wo (H, hd, d)
* caches:  k/v (B, S_max, KV, hd); MLA caches the *compressed* (c_kv, k_rope)
  pair instead — the memory win that defines MLA.

The blockwise path (scan over KV blocks with running max/denominator) is the
pure-JAX oracle for the Pallas flash kernel in ``repro/kernels/flash_attention``
and keeps prefill memory O(S·block) instead of O(S²).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers
from repro.sharding import logical_constraint

Array = jax.Array

NEG_INF = -2.0**30  # large-but-finite: avoids NaNs from (-inf) - (-inf)


# ---------------------------------------------------------------------------
# parameter init / specs
# ---------------------------------------------------------------------------


def init_gqa(key, cfg) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.trunc_normal(ks[0], (d, H, hd), 1.0, cfg.param_dtype),
        "wk": layers.trunc_normal(ks[1], (d, KV, hd), 1.0, cfg.param_dtype),
        "wv": layers.trunc_normal(ks[2], (d, KV, hd), 1.0, cfg.param_dtype),
        "wo": layers.trunc_normal(ks[3], (H, hd, d), 1.0, cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(hd, cfg.param_dtype)
        p["k_norm"] = layers.init_rmsnorm(hd, cfg.param_dtype)
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H, hd), cfg.param_dtype)
        p["bk"] = jnp.zeros((KV, hd), cfg.param_dtype)
        p["bv"] = jnp.zeros((KV, hd), cfg.param_dtype)
        p["bo"] = jnp.zeros((d,), cfg.param_dtype)
    return p


def gqa_spec(cfg) -> dict:
    p = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.rmsnorm_spec()
        p["k_norm"] = layers.rmsnorm_spec()
    if cfg.attn_bias:
        p.update({"bq": ("heads", None), "bk": ("kv_heads", None),
                  "bv": ("kv_heads", None), "bo": ("embed",)})
    return p


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------


def _expand_kv(k: Array, n_rep: int) -> Array:
    """GQA: repeat KV heads to match query heads. (B,S,KV,hd)->(B,S,KV*rep,hd)"""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd
    )


def full_attention(q: Array, k: Array, v: Array, *, causal: bool,
                   q_offset=0) -> Array:
    """Materialized-scores attention (small sequences / oracle)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return out


def blockwise_attention(q: Array, k: Array, v: Array, *, causal: bool,
                        block_kv: int = 1024, q_offset=0) -> Array:
    """Online-softmax attention, scanning KV blocks: O(S·block) memory.

    Oracle twin of the Pallas flash kernel.  Handles causal masking per
    block; `q_offset` shifts query positions (for chunked prefill).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if sk % block_kv != 0:
        # fall back to padded full for odd sizes (tests); production shapes divide
        return full_attention(q, k, v, causal=causal, q_offset=q_offset)
    nblk = sk // block_kv
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    kb = k.reshape(b, nblk, block_kv, h, hd)
    vb = v.reshape(b, nblk, block_kv, h, hd)
    qpos = jnp.arange(sq) + q_offset

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, blk_idx = inputs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32))
        if causal:
            kpos = blk_idx * block_kv + jnp.arange(block_kv)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (b,h,q,d)->(b,q,h,d)


# ---------------------------------------------------------------------------
# GQA layer: train/prefill and decode
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
    if "page_ro" in cache:
        # COW prefix sharing: a page mapped by >1 sequence is
        # write-protected — the pool manager forks before any legitimate
        # write reaches one, so a scatter aimed at it means host and device
        # state disagree; drop it like an overflow write rather than corrupt
        # the co-holder.  Only the scatter is rerouted — the attention
        # gather still reads shared pages through the table.
        ro = cache["page_ro"][jnp.minimum(phys, n_pages - 1)]
        phys = jnp.where(ro, n_pages, phys)
    gather_table = table
    if "page_hot" in cache:
        # tiered residency: a non-hot page's bytes live in the host tier
        # (demoted) or are mid-migration — the engine never decodes such a
        # slot, so a table entry still aimed at one means residency
        # bookkeeping and device state disagree.  Drop scatters at it like
        # overflow writes and reroute the gather to the (all-zero,
        # always-hot) parking page rather than read a physical page the
        # pool may have re-issued.
        hot = cache["page_hot"]
        phys = jnp.where(hot[jnp.minimum(phys, n_pages - 1)], phys, n_pages)
        gather_table = jnp.where(hot[table], table, n_pages - 1)
    return phys, cols % pt, gather_table


def _paged_write(cache: dict, new: dict, cols: Array):
    """Scatter each pool's new tokens (``new``: pool name -> (B, S,
    *feature)) into each row's pages (:func:`_page_routes`) and gather every
    row's logical view of each pool through its page table.  Returns the
    new cache and the views, (B, pages·pt, *feature) each, in ``new``'s
    order.

    A page is ``pt`` rows of one token each, or, in a pool of a narrow
    feature, rows of several tokens side by side
    (:func:`repro.serve.disagg.page_shape`); a token's window starts at its
    row and its lane offset in that row.  A cache that carries a ``layer``
    index holds its pools stacked over the scanned layers
    (``transformer.apply_stack`` carries them through the layer scan): the
    scatter and the gather index the stacked pool at that layer directly,
    so no layer's pool is sliced out or written back."""
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


def gqa_attention(
    params: dict,
    x: Array,
    cfg,
    *,
    positions: Array,
    causal: bool = True,
    cache: dict | None = None,
    block_kv: int = 1024,
    kv_input: Array | None = None,  # cross-attention: encoder output
    cross_cached: bool = False,     # static: cross KV already in the cache
) -> tuple[Array, dict | None]:
    """GQA attention over ``x`` (B, S, d).

    With ``cache``: decode path — S is the new-token count (typically 1); the
    cache is updated in place (functionally) at ``cache['pos']``.
    With ``kv_input``: cross-attention (keys/values from the encoder);
    ``cross_cached=True`` (decode) reads the precomputed encoder KV from the
    cache instead of recomputing it.
    Returns (output (B,S,d), new_cache).
    """
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)

    if kv_input is not None and cross_cached:
        # cross-attention with precomputed encoder KV
        k, v = cache["k"].astype(dt), cache["v"].astype(dt)
        new_cache = cache
    else:
        src = kv_input if kv_input is not None else x
        k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(dt))
        if "bk" in params:
            k = k + params["bk"].astype(dt)
            v = v + params["bv"].astype(dt)
        new_cache = cache

    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        if not (kv_input is not None and cross_cached):
            k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)

    if cfg.rope_theta and kv_input is None:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)

    if cache is not None and kv_input is None:
        # decode: write new kv at each row's position, attend over the prefix
        pos = cache["pos"]  # (B,) int32: per-row current length
        rows = jnp.arange(B)[:, None]
        cols = pos[:, None] + jnp.arange(S)[None, :]
        if "k_pages" in cache:
            # paged KV: the cache is a physical page pool + per-row page
            # table (the decode-side PagedKVWindow layout).  New tokens
            # scatter into the row's current physical page; attention
            # gathers the row's pages back into a contiguous logical view.
            new_cache, (ck, cv) = _paged_write(
                cache, {"k_pages": k, "v_pages": v}, cols)  # (B, pages·pt, KV, hd)
            new_cache["pos"] = pos + S
            ck = logical_constraint(ck, "batch", "kv_seq", "kv_heads", None)
            cv = logical_constraint(cv, "batch", "kv_seq", "kv_heads", None)
        else:
            ck = cache["k"].at[rows, cols].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[rows, cols].set(v.astype(cache["v"].dtype))
            ck = logical_constraint(ck, "batch", "kv_seq", "kv_heads", None)
            cv = logical_constraint(cv, "batch", "kv_seq", "kv_heads", None)
            new_cache = dict(cache, k=ck, v=cv, pos=pos + S)
        # scores take the operands in the compute dtype and accumulate in
        # f32 (a bf16 product is exact there); the softmax runs in f32
        rep = H // KV
        S_max = ck.shape[1]
        scale = hd ** -0.5
        ck, cv = ck.astype(dt), cv.astype(dt)
        if S == 1:
            # decode: query head h = g·rep + r reads KV head g (the order
            # _expand_kv gives), so the rep heads of a group contract
            # against one read of the cache, never repeated to H heads
            qg = q.reshape(B, S, KV, rep, hd)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ck,
                                preferred_element_type=jnp.float32)
            scores = scores.reshape(B, H, S, S_max)
        else:
            # a multi-token query (prefill into a cache) keeps the repeat:
            # it is small next to the (B, H, S, S_max) f32 scores, and the
            # grouped contraction costs one more pass over them (TPU v5e,
            # a 3072-token prefill: 20% slower grouped)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, _expand_kv(ck, rep),
                                preferred_element_type=jnp.float32)
        kpos = jnp.arange(S_max)
        qpos = pos[:, None] + jnp.arange(S)[None, :]              # (B, S)
        mask = qpos[:, None, :, None] >= kpos[None, None, None, :]  # (B,1,S,K)
        scores = jnp.where(mask, scores * scale, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        if S == 1:
            out = jnp.einsum("bgrqk,bkgd->bqgrd",
                             w.reshape(B, KV, rep, S, S_max), cv)
            out = out.reshape(B, S, H, hd)
        else:
            out = jnp.einsum("bhqk,bkhd->bqhd", w, _expand_kv(cv, rep))
    else:
        kk = _expand_kv(k, H // KV)
        vv = _expand_kv(v, H // KV)
        impl = cfg.attn_impl
        if impl == "auto":
            impl = ("blockwise" if S * kk.shape[1] > cfg.blockwise_threshold
                    and kv_input is None else "full")
        if impl == "stub":
            # projections + value passthrough: isolates the quadratic part's
            # traffic for kernel-substitution roofline modelling (§Perf)
            out = (vv + 0.0 * q).astype(q.dtype)
        elif impl == "blockwise" and kv_input is None:
            out = blockwise_attention(q, kk, vv, causal=causal, block_kv=block_kv)
        else:
            out = full_attention(q, kk, vv, causal=causal and kv_input is None)
        if kv_input is not None and cache is not None and not cross_cached:
            # prefill: memoize the encoder KV for decode
            new_cache = dict(cache, k=k.astype(cache["k"].dtype),
                             v=v.astype(cache["v"].dtype))

    out = logical_constraint(out, "batch", "seq", "heads", None)
    proj = jnp.einsum("bshk,hkd->bsd", out.astype(dt), params["wo"].astype(dt))
    if "bo" in params:
        proj = proj + params["bo"].astype(dt)
    return proj, new_cache


def init_gqa_cache(cfg, batch: int, max_seq: int, dtype) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, KV, hd), dtype),
        "v": jnp.zeros((batch, max_seq, KV, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def gqa_cache_spec(cfg) -> dict:
    return {
        "k": ("batch", "kv_seq", "kv_heads", None),
        "v": ("batch", "kv_seq", "kv_heads", None),
        "pos": ("batch",),
    }


def init_paged_gqa_cache(cfg, batch: int, max_seq: int, dtype,
                         page_tokens: int) -> dict:
    """Paged-layout GQA cache: a physical page pool + per-row page table.

    The pool holds ``batch · max_seq / page_tokens`` allocatable pages plus
    one **parking page**; which physical page backs logical block *b* of
    row *r* is the serving engine's page allocator's decision
    (``page_table[r, b]``), exactly the indirection a decode-side
    :class:`repro.serve.paged.PagedKVWindow` pool gives a disaggregated
    deployment.  One definition of the layout exists —
    ``repro.serve.disagg.paginate_cache`` — and this constructor delegates
    to it, so the pool/parking/table invariants cannot drift."""
    from repro.serve.disagg import paginate_cache

    return paginate_cache(init_gqa_cache(cfg, batch, max_seq, dtype),
                          page_tokens)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 7)
    pd = cfg.param_dtype
    qk = m.qk_nope + m.qk_rope
    if m.q_lora:
        p = {"w_dq": layers.trunc_normal(ks[0], (d, m.q_lora), 1.0, pd),
             "q_norm": layers.init_rmsnorm(m.q_lora, pd),
             "w_uq": layers.trunc_normal(ks[1], (m.q_lora, H, qk), 1.0, pd)}
    else:
        p = {"w_q": layers.trunc_normal(ks[1], (d, H, qk), 1.0, pd)}
    return dict(
        p,
        w_dkv=layers.trunc_normal(ks[2], (d, m.kv_lora), 1.0, pd),
        kv_norm=layers.init_rmsnorm(m.kv_lora, pd),
        w_kr=layers.trunc_normal(ks[3], (d, m.qk_rope), 1.0, pd),
        w_uk=layers.trunc_normal(ks[4], (m.kv_lora, H, m.qk_nope), 1.0, pd),
        w_uv=layers.trunc_normal(ks[5], (m.kv_lora, H, m.v_head), 1.0, pd),
        wo=layers.trunc_normal(ks[6], (H, m.v_head, d), 1.0, pd),
    )


def mla_spec(cfg) -> dict:
    if cfg.mla.q_lora:
        p = {"w_dq": ("embed", "q_lora"), "q_norm": layers.rmsnorm_spec(),
             "w_uq": ("q_lora", "heads", None)}
    else:
        p = {"w_q": ("embed", "heads", None)}
    return dict(
        p,
        w_dkv=("embed", "kv_lora"),
        kv_norm=layers.rmsnorm_spec(),
        w_kr=("embed", None),
        w_uk=("kv_lora", "heads", None),
        w_uv=("kv_lora", "heads", None),
        wo=("heads", None, "embed"),
    )


def mla_softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times ``mscale(factor,
    mscale_all_dim)²`` under YaRN (DeepSeek-V2's softmax temperature)."""
    m, yarn = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= layers.yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def _mla_blockwise(q, c_all, kr_all, params, *, qpos, n_keys, scale,
                   block_kv: int):
    """Causal MLA over the first ``n_keys`` positions of the latent
    ``c_all`` (B, S_k, r) and ``kr_all`` (B, S_k, rope), in the expanded
    form: K = [c·W_uk, k_rope] and V = c·W_uv are built from the latent
    once (a few tens of MB a layer), then attended block by block with an
    online softmax, so no ``(H, S, S_k)`` score tensor ever exists.  Queries
    go in blocks too, and each query block visits only the key blocks at or
    before its last position (dynamic trip counts): about half the pairs of
    a prompt, where the score blocks' f32 passes set the time.  ``q`` (B, S,
    H, nope+rope); ``qpos`` (B, S).  Returns (B, S, H, v_head) in ``q``'s
    dtype."""
    B, S, H, D = q.shape
    dt = q.dtype
    S_k = c_all.shape[1]
    bk = min(block_kv, S_k)
    bq = min(block_kv, S)
    c_all = jnp.pad(c_all, ((0, 0), (0, (-S_k) % bk), (0, 0))).astype(dt)
    kr_all = jnp.pad(kr_all, ((0, 0), (0, (-S_k) % bk), (0, 0))).astype(dt)
    k_nope = jnp.einsum("btr,rhk->bthk", c_all, params["w_uk"].astype(dt))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr_all[:, :, None, :], k_nope.shape[:3] + kr_all.shape[-1:])], axis=-1)
    v = jnp.einsum("btr,rhv->bthv", c_all, params["w_uv"].astype(dt))
    v_head = v.shape[-1]
    n_kb = (n_keys + bk - 1) // bk
    pad_q = (-S) % bq
    # padded queries sit at position -1: they see no key and are dropped
    qs = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    ps = jnp.pad(qpos, ((0, 0), (0, pad_q)), constant_values=-1)
    nq = qs.shape[1] // bq
    qs = jnp.moveaxis(qs.reshape(B, nq, bq, H, D), 1, 0)
    ps = jnp.moveaxis(ps.reshape(B, nq, bq), 1, 0)

    def q_block(args):
        qb, pb = args

        def body(i, carry):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, i * bk, bk, axis=1)
            vb = lax.dynamic_slice_in_dim(v, i * bk, bk, axis=1)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                            preferred_element_type=jnp.float32) * scale
            kpos = i * bk + jnp.arange(bk)
            sc = jnp.where(pb[:, None, :, None] >= kpos, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(dt), vb,
                preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(axis=-1), acc

        init = (jnp.full((B, H, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, bq), jnp.float32),
                jnp.zeros((B, H, bq, v_head), jnp.float32))
        if isinstance(n_kb, int):
            # no cache (training): a static count keeps it differentiable
            trips = n_kb
        else:
            trips = jnp.minimum(jnp.max(pb) // bk + 1, n_kb)  # blocks it sees
        _, l, acc = lax.fori_loop(0, trips, body, init)
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dt)

    out = lax.map(q_block, (qs, ps))              # (nq, B, H, bq, v_head)
    out = jnp.moveaxis(out, 0, 2).reshape(B, H, nq * bq, v_head)[:, :, :S]
    return jnp.moveaxis(out, 1, 2)                # (B, S, H, v_head)


def _mla_absorbed(q_nope, q_rope, c_all, kr_all, params, *, qpos, scale):
    """MLA in the absorbed form, for a decode step: the query goes through
    ``W_uk`` into the latent space and scores against the cached ``c_kv``
    directly (plus ``q_rope·k_rope``); the weights average the latent and
    ``W_uv`` expands the result once -- the cache is read as stored, never
    expanded.  Operands in the compute dtype, f32 accumulation, f32
    softmax (as the GQA decode)."""
    dt = q_nope.dtype
    S_k = c_all.shape[1]
    c_all, kr_all = c_all.astype(dt), kr_all.astype(dt)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"].astype(dt))
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_all,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshk,btk->bhst", q_rope, kr_all,
                           preferred_element_type=jnp.float32)) * scale
    mask = qpos[:, None, :, None] >= jnp.arange(S_k)[None, None, None, :]
    w = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1).astype(dt)
    ctx = jnp.einsum("bhst,btr->bshr", w, c_all)
    return jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"].astype(dt))


def mla_attention(
    params: dict,
    x: Array,
    cfg,
    *,
    positions: Array,
    cache: dict | None = None,
    block_kv: int = 1024,
) -> tuple[Array, dict | None]:
    """DeepSeek-V2 multi-head latent attention.

    The KV cache stores only (c_kv: kv_lora, k_rope: qk_rope) per token --
    the compression that makes many-head attention servable -- densely
    (``{c_kv, k_rope, pos}``) or in pages (``{ckv_pages, kr_pages,
    page_table, ...}``, :func:`repro.serve.disagg.paginate_cache`).  A
    one-token step against a cache (decode) attends in the absorbed form
    (:func:`_mla_absorbed`); a prompt, with or without a cache, in the
    expanded form over key blocks (:func:`_mla_blockwise`).
    """
    m = cfg.mla
    B, S, d = x.shape
    dt = x.dtype
    yarn = cfg.rope_scaling

    if "w_q" in params:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"].astype(dt))
    else:
        cq = layers.rms_norm(jnp.einsum("bsd,dr->bsr", x, params["w_dq"].astype(dt)),
                             params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"].astype(dt))
    q_nope, q_rope = q[..., : m.qk_nope], q[..., m.qk_nope:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta, yarn)

    c_kv = layers.rms_norm(jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(dt)),
                           params["kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, params["w_kr"].astype(dt))
    k_rope = layers.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                               yarn)[:, :, 0]

    if cache is not None:
        pos = cache["pos"]  # (B,)
        cols = pos[:, None] + jnp.arange(S)[None, :]
        if "ckv_pages" in cache:
            new_cache, (c_all, kr_all) = _paged_write(
                cache, {"ckv_pages": c_kv, "kr_pages": k_rope}, cols)
        else:
            rows = jnp.arange(B)[:, None]
            c_all = cache["c_kv"].at[rows, cols].set(c_kv.astype(cache["c_kv"].dtype))
            kr_all = cache["k_rope"].at[rows, cols].set(
                k_rope.astype(cache["k_rope"].dtype))
            new_cache = dict(cache, c_kv=c_all, k_rope=kr_all)
        new_cache["pos"] = pos + S
        qpos = cols
        n_keys = jnp.max(pos) + S
    else:
        new_cache = None
        c_all, kr_all = c_kv, k_rope
        qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
        n_keys = S

    scale = mla_softmax_scale(cfg)
    if cache is not None and S == 1:
        out = _mla_absorbed(q_nope, q_rope, c_all, kr_all, params,
                            qpos=qpos, scale=scale)
    else:
        out = _mla_blockwise(jnp.concatenate([q_nope, q_rope], axis=-1),
                             c_all, kr_all, params, qpos=qpos, n_keys=n_keys,
                             scale=scale, block_kv=block_kv)
    out = logical_constraint(out, "batch", "seq", "heads", None)
    proj = jnp.einsum("bshv,hvd->bsd", out, params["wo"].astype(dt))
    return proj, new_cache


def init_mla_cache(cfg, batch: int, max_seq: int, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_seq, m.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_seq, m.qk_rope), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def mla_cache_spec(cfg) -> dict:
    return {
        "c_kv": ("batch", "kv_seq", "kv_lora"),
        "k_rope": ("batch", "kv_seq", None),
        "pos": ("batch",),
    }


__all__ = [
    "init_gqa", "gqa_spec", "gqa_attention", "init_gqa_cache", "gqa_cache_spec",
    "init_paged_gqa_cache",
    "init_mla", "mla_spec", "mla_attention", "mla_softmax_scale",
    "init_mla_cache", "mla_cache_spec",
    "full_attention", "blockwise_attention",
]
