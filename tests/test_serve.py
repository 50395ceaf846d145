"""Serving tests: continuous-batching engine greedy-correctness + paged window."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.tiny import tiny_config
from repro.models import build_model
from repro.models.paged_cache import paginate_cache
from repro.serve.engine import Request, ServeEngine

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny_config("qwen3-4b")
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def test_engine_matches_reference_greedy(model_and_params):
    cfg, m, params = model_and_params
    rng = np.random.RandomState(0)
    req = Request(rid=1, prompt=rng.randint(0, cfg.vocab, size=7),
                  max_new_tokens=5)
    eng = ServeEngine(m, params, n_slots=2, max_seq=64)
    eng.submit(req)
    out = eng.run()[0].tokens
    toks = list(req.prompt)
    ref = []
    for _ in range(5):
        logits, _ = m.forward(params, {"tokens": jnp.asarray(toks, jnp.int32)[None]})
        nxt = int(jnp.argmax(logits[0, -1]))
        ref.append(nxt)
        toks.append(nxt)
    assert out == ref


def test_engine_continuous_batching_all_complete(model_and_params):
    cfg, m, params = model_and_params
    rng = np.random.RandomState(1)
    eng = ServeEngine(m, params, n_slots=3, max_seq=64)
    for rid in range(7):
        eng.submit(Request(rid=rid,
                           prompt=rng.randint(0, cfg.vocab, size=4 + rid % 5),
                           max_new_tokens=3 + rid % 4))
    done = eng.run()
    assert sorted(c.rid for c in done) == list(range(7))
    for c in done:
        assert 3 <= len(c.tokens) <= 7


def test_engine_batched_equals_sequential(model_and_params):
    """Requests decoded concurrently in slots produce the same tokens as
    decoded alone (slot isolation — per-row cache positions)."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(2)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=5 + 3 * i),
                    max_new_tokens=4) for i in range(3)]
    eng = ServeEngine(m, params, n_slots=3, max_seq=64)
    for r in reqs:
        eng.submit(r)
    together = {c.rid: c.tokens for c in eng.run()}
    for r in reqs:
        solo = ServeEngine(m, params, n_slots=1, max_seq=64)
        solo.submit(Request(rid=r.rid, prompt=r.prompt, max_new_tokens=4))
        assert solo.run()[0].tokens == together[r.rid], f"slot isolation rid={r.rid}"


def test_engine_max_new_tokens_one_stops_at_prefill(model_and_params):
    """A max_new_tokens=1 request is complete at admission: exactly one
    token (the prefill argmax), no extra decode step."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab, size=6)
    eng = ServeEngine(m, params, n_slots=2, max_seq=64)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=1))
    done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 1
    # the single token is the greedy prefill continuation
    logits, _ = m.forward(params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    assert done[0].tokens == [int(jnp.argmax(logits[0, -1]))]


def _first_greedy_token(m, params, prompt):
    logits, _ = m.forward(params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    return int(jnp.argmax(logits[0, -1]))


def test_engine_first_token_eos_releases_slot(model_and_params):
    """A prompt whose first generated token is EOS completes at admission
    and frees its slot in the same tick — not a full tick later."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, cfg.vocab, size=5)
    eos = _first_greedy_token(m, params, prompt)
    eng = ServeEngine(m, params, n_slots=1, max_seq=64)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, eos_id=eos))
    eng.step()   # admission tick: must complete and release immediately
    assert eng.done and eng.done[0].tokens == [eos]
    assert eng.slot_free == [True] and not eng.slot_req


def test_paged_engine_first_token_eos_frees_pages(model_and_params):
    """In paged mode, admission-time completion must return the slot's KV
    pages to the allocator (they were leaked for an extra tick before)."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab, size=6)
    eos = _first_greedy_token(m, params, prompt)
    eng = ServeEngine(m, params, n_slots=2, max_seq=32, paged_kv=True,
                      page_tokens=8)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8, eos_id=eos))
    eng.step()
    st = eng.stats()
    assert eng.done and eng.done[0].tokens == [eos]
    assert st["pages_freed"] == st["pages_allocated"] == 32 // 8
    assert st["pages_free"] == 2 * (32 // 8)


def test_paged_engine_max_new_tokens_one(model_and_params):
    """max_new_tokens=1 on the paged engine: one token, pages freed, and the
    slot is immediately reusable by the next pending request."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(8)
    eng = ServeEngine(m, params, n_slots=1, max_seq=32, paged_kv=True,
                      page_tokens=8)
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=rng.randint(0, cfg.vocab, size=4),
                           max_new_tokens=1))
    done = eng.run()
    assert sorted(c.rid for c in done) == [0, 1, 2]
    assert all(len(c.tokens) == 1 for c in done)
    st = eng.stats()
    assert st["pages_freed"] == st["pages_allocated"] == 3 * (32 // 8)


def test_engine_rejects_oversized_prompt(model_and_params):
    cfg, m, params = model_and_params
    eng = ServeEngine(m, params, n_slots=1, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(Request(rid=0, prompt=np.zeros(16, np.int32),
                           max_new_tokens=1))


def test_paged_window_multidevice():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "mdev", "paged_window.py")],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.join(HERE, ".."))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "PAGED WINDOW OK" in proc.stdout


# ---------------------------------------------------------------------------
# disaggregated serving: the paged-KV engine + the SPMD round trip
# ---------------------------------------------------------------------------


def test_paged_engine_matches_dense_greedy(model_and_params):
    """The page-table indirection must be a pure layout change: paged and
    dense engines produce identical greedy decodes for identical requests."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(3)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=4 + 3 * i),
                    max_new_tokens=4) for i in range(3)]
    dense = ServeEngine(m, params, n_slots=2, max_seq=64)
    paged = ServeEngine(m, params, n_slots=2, max_seq=64,
                        paged_kv=True, page_tokens=8)
    for r in reqs:
        dense.submit(Request(r.rid, r.prompt, r.max_new_tokens))
        paged.submit(Request(r.rid, r.prompt, r.max_new_tokens))
    d = {c.rid: c.tokens for c in dense.run()}
    p = {c.rid: c.tokens for c in paged.run()}
    assert d == p


def test_paged_engine_page_churn_reuses_pages(model_and_params):
    """More requests than slots: pages are freed at release and re-allocated
    to later admissions — the decode of a re-using slot must not be polluted
    by the previous tenant (parking + page-table rewire)."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(4)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=4 + i % 4),
                    max_new_tokens=3 + i % 3) for i in range(6)]
    paged = ServeEngine(m, params, n_slots=2, max_seq=32,
                        paged_kv=True, page_tokens=8)
    for r in reqs:
        paged.submit(r)
    done = {c.rid: c.tokens for c in paged.run()}
    assert sorted(done) == list(range(6))
    st = paged.stats()
    assert st["pages_allocated"] == 6 * (32 // 8)
    assert st["pages_freed"] == st["pages_allocated"]
    assert st["pages_free"] == 2 * (32 // 8)
    # every request decodes exactly as it would alone on a dense engine
    for r in reqs:
        solo = ServeEngine(m, params, n_slots=1, max_seq=32)
        solo.submit(Request(r.rid, r.prompt, r.max_new_tokens))
        assert solo.run()[0].tokens == done[r.rid], f"rid={r.rid}"


def test_paged_engine_rejects_indivisible_page_size(model_and_params):
    cfg, m, params = model_and_params
    with pytest.raises(ValueError, match="not divisible"):
        ServeEngine(m, params, n_slots=1, max_seq=20, paged_kv=True,
                    page_tokens=16)


def test_paged_engine_rejects_archs_without_gqa_kv():
    """paged_kv on a stack with no self-attention KV (pure SSM) must refuse
    instead of silently serving dense while reporting page activity."""
    cfg = tiny_config("mamba2-370m")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no self-attention KV"):
        ServeEngine(m, params, n_slots=1, max_seq=32, paged_kv=True,
                    page_tokens=8)


def test_init_paged_gqa_cache_matches_paginated_dense():
    """A decode step through a GQA cache paginated from a fresh dense one,
    its row 0 wired to real pages, matches the dense decode."""
    from repro.models import attention

    cfg = tiny_config("qwen3-4b")
    B, S, pt = 2, 16, 4
    dense = attention.init_gqa_cache(cfg, B, S, jnp.float32)
    paged = paginate_cache(dense, pt)
    # wire row 0 to real pages and decode one token: paged == dense
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 1, cfg.d_model), jnp.float32)
    params = attention.init_gqa(jax.random.PRNGKey(1), cfg)
    paged = dict(paged,
                 page_table=paged["page_table"].at[0].set(
                     jnp.arange(S // pt)))
    positions = jnp.zeros((B, 1), jnp.int32)
    out_d, _ = attention.gqa_attention(params, x, cfg, positions=positions,
                                       cache=dense)
    out_p, new_p = attention.gqa_attention(params, x, cfg,
                                           positions=positions, cache=paged)
    np.testing.assert_allclose(np.asarray(out_d[0]), np.asarray(out_p[0]),
                               rtol=1e-5, atol=1e-5)
    assert new_p["pos"].tolist() == [1, 1]


def test_paged_decode_drops_overflow_writes_like_dense():
    """A row at pos == max_seq has no page for the new token: the paged
    scatter must drop it (as the dense layout's OOB write is dropped), not
    clamp onto the row's last page and corrupt its first KV slot."""
    from repro.models import attention

    cfg = tiny_config("qwen3-4b")
    B, S, pt = 1, 8, 4
    params = attention.init_gqa(jax.random.PRNGKey(1), cfg)
    paged = paginate_cache(attention.init_gqa_cache(cfg, B, S, jnp.float32), pt)
    paged = dict(paged,
                 page_table=paged["page_table"].at[0].set(jnp.arange(S // pt)),
                 k_pages=paged["k_pages"] + 3.0,
                 v_pages=paged["v_pages"] + 3.0,
                 pos=jnp.full((B,), S, jnp.int32))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 1, cfg.d_model), jnp.float32)
    positions = jnp.full((B, 1), S, jnp.int32)
    _, new = attention.gqa_attention(params, x, cfg, positions=positions,
                                     cache=paged)
    np.testing.assert_array_equal(np.asarray(new["k_pages"]),
                                  np.asarray(paged["k_pages"]))
    np.testing.assert_array_equal(np.asarray(new["v_pages"]),
                                  np.asarray(paged["v_pages"]))


def test_disagg_round_trip_multidevice():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "mdev", "serve_disagg.py")],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.join(HERE, ".."))
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "SERVE DISAGG OK" in proc.stdout


# ---------------------------------------------------------------------------
# scheduler layer: admission policies
# ---------------------------------------------------------------------------


def test_scheduler_policy_selection_order():
    from repro.serve.scheduler import Scheduler

    class R:  # minimal request stand-in
        def __init__(self, rid, priority=0, tenant=0):
            self.rid, self.priority, self.tenant = rid, priority, tenant

    pr = Scheduler(4, "priority")
    for rid, p in [(0, 0), (1, 5), (2, 1)]:
        pr.submit(R(rid, priority=p))
    picked = pr.select(3, live=0)
    assert [e.req.rid for e in picked] == [1, 2, 0]   # priority desc, FIFO tie

    fair = Scheduler(4, "fair")
    for rid, t in [(0, 0), (1, 0), (2, 1)]:
        fair.submit(R(rid, tenant=t))
    picked = fair.select(3, live=0)
    assert [e.req.rid for e in picked] == [0, 2, 1]   # alternate tenants

    st = Scheduler(4, "static")
    st.submit(R(0))
    assert st.select(2, live=1) == []                 # drain before refill
    assert [e.req.rid for e in st.select(2, live=0)] == [0]

    with pytest.raises(ValueError, match="unknown policy"):
        Scheduler(2, "lifo")


def test_scheduler_requeue_restores_order_and_counters():
    from repro.serve.scheduler import Scheduler

    class R:
        def __init__(self, rid):
            self.rid, self.priority, self.tenant = rid, 0, 0

    s = Scheduler(2, "continuous")
    for rid in range(3):
        s.submit(R(rid))
    picked = s.select(2, live=0)
    assert [e.req.rid for e in picked] == [0, 1] and s.admitted == 2
    s.requeue(picked[1])
    s.requeue(picked[0])
    assert [e.req.rid for e in s.pending_entries()] == [0, 1, 2]
    assert s.admitted == 0
    assert s.ticket_window(live=1) == 1 and s.ticket_window(live=2) == 0


def test_engine_priority_policy_orders_admission(model_and_params):
    cfg, m, params = model_and_params
    rng = np.random.RandomState(20)
    eng = ServeEngine(m, params, n_slots=1, max_seq=32, policy="priority")
    eng.submit(Request(rid=0, prompt=rng.randint(0, cfg.vocab, size=5),
                       max_new_tokens=2, priority=0))
    eng.submit(Request(rid=1, prompt=rng.randint(0, cfg.vocab, size=5),
                       max_new_tokens=2, priority=9))
    done = eng.run()
    assert [c.rid for c in eng.done] == [1, 0]   # high priority served first
    assert all(c.finished for c in done)


def test_engine_static_policy_drains_whole_batch(model_and_params):
    cfg, m, params = model_and_params
    rng = np.random.RandomState(21)
    eng = ServeEngine(m, params, n_slots=2, max_seq=32, policy="static")
    for rid, mn in [(0, 2), (1, 6), (2, 2)]:
        eng.submit(Request(rid=rid, prompt=rng.randint(0, cfg.vocab, size=5),
                           max_new_tokens=mn))
    eng.step()   # admits the r0+r1 batch; r0 completes this tick
    assert eng.scheduler.pending_count == 1 and len(eng.slot_req) == 1
    eng.step()   # a slot is free but r1 still live: static admits nothing
    assert eng.scheduler.pending_count == 1
    done = eng.run()
    assert sorted(c.rid for c in done) == [0, 1, 2]


# ---------------------------------------------------------------------------
# pool layer: refcounts, COW, double-free guards
# ---------------------------------------------------------------------------


def test_kv_pool_manager_refcount_share_release():
    from repro.serve.paged import KVPoolManager

    pool = KVPoolManager(6)
    assert pool.alloc(3) == [0, 1, 2] and pool.n_free == 3
    pool.share_pages([0, 1])
    assert pool.refcount_of(0) == 2 and pool.shared_maps == 2
    dropped = pool.release([0, 1, 2])
    assert set(dropped) == {0, 1, 2}       # 0,1 -> refcount 1; 2 -> freed
    assert pool.n_free == 4 and pool.refcount_of(0) == 1
    pool.release([0, 1])
    assert pool.n_free == 6 and pool.frees == 3
    with pytest.raises(ValueError, match=r"release\(2\).*double free"):
        pool.release([2])
    with pytest.raises(ValueError, match=r"share_pages\(5\)"):
        pool.share_pages([5])
    assert pool.alloc(6) == [3, 4, 5, 2, 0, 1]   # FIFO reuse order


def test_kv_pool_manager_cow_fork_and_debt():
    from repro.serve.paged import KVPoolManager

    pool = KVPoolManager(4)
    [p] = pool.alloc(1)
    pool.share_pages([p], writable=True)
    assert pool.cow_debt == 1
    assert not pool.can_admit(3)           # 3 free - 1 reserved < 3
    assert pool.can_admit(2)
    new, copied = pool.cow_write(p)
    assert copied and new != p
    assert pool.refcount_of(p) == 1 and pool.refcount_of(new) == 1
    assert pool.cow_debt == 0 and pool.cow_copies == 1
    same, copied2 = pool.cow_write(new)    # sole owner: write in place
    assert same == new and not copied2
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(5)


def test_kv_pool_manager_cow_fork_without_free_page_raises():
    from repro.serve.paged import KVPoolManager

    pool = KVPoolManager(1)
    [p] = pool.alloc(1)
    pool.share_pages([p], writable=True)
    with pytest.raises(RuntimeError, match="fork"):
        pool.cow_write(p)


def test_paged_window_free_page_double_free_raises():
    """Regression (satellite): freeing a non-live page must raise with the
    page id instead of silently bumping the epoch past outstanding-handle
    checks and re-arming a dead slot."""
    from repro.serve.paged import PagedKVWindow, PageSpec

    spec = PageSpec(page_tokens=2, kv_heads=1, head_dim=2, n_pages=3)
    pool = PagedKVWindow.create(spec, "x", 1, dtype=jnp.float32)
    pool = pool.alloc_page(1)
    pool = pool.free_page(1)
    with pytest.raises(ValueError, match=r"free_page\(1\)"):
        pool.free_page(1)                  # double free
    with pytest.raises(ValueError, match=r"free_page\(2\)"):
        pool.free_page(2)                  # never allocated
    with pytest.raises(ValueError, match=r"free_page\(7\)"):
        pool.free_page(7)                  # out of range


# ---------------------------------------------------------------------------
# executor/facade: run() incompleteness, engine construction guards
# ---------------------------------------------------------------------------


def test_engine_run_returns_explicit_incomplete(model_and_params):
    """Satellite: exhausting max_ticks must not silently drop in-flight
    sequences — they come back as finished=False completions, counted in
    stats(), and the engine stays resumable."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(22)
    eng = ServeEngine(m, params, n_slots=1, max_seq=64)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=rng.randint(0, cfg.vocab, size=5),
                           max_new_tokens=8))
    out = eng.run(max_ticks=3)
    inc = {c.rid: c for c in out if not c.finished}
    assert set(inc) == {0, 1}
    assert len(inc[0].tokens) == 4         # prefill token + 3 decode ticks
    assert inc[1].tokens == []             # never admitted
    assert eng.stats()["incomplete"] == 2
    out2 = eng.run()                       # resumable: finish the rest
    assert sorted(c.rid for c in out2 if c.finished) == [0, 1]
    assert eng.stats()["incomplete"] == 0
    for c in out2:
        assert c.done_tick >= c.arrival_tick


def test_engine_run_strict_raises_on_incomplete(model_and_params):
    cfg, m, params = model_and_params
    rng = np.random.RandomState(23)
    eng = ServeEngine(m, params, n_slots=1, max_seq=64)
    eng.submit(Request(rid=7, prompt=rng.randint(0, cfg.vocab, size=5),
                       max_new_tokens=10))
    with pytest.raises(RuntimeError, match=r"unfinished.*7"):
        eng.run(max_ticks=2, strict=True)


def test_engine_rejects_bad_sharing_configs(model_and_params):
    cfg, m, params = model_and_params
    with pytest.raises(ValueError, match="prefix_share"):
        ServeEngine(m, params, n_slots=1, max_seq=32, prefix_share=True)
    with pytest.raises(ValueError, match="kv_pages"):
        ServeEngine(m, params, n_slots=2, max_seq=32, paged_kv=True,
                    page_tokens=8, kv_pages=2)   # below pages_per_slot


# ---------------------------------------------------------------------------
# COW prefix sharing: bit-identity property sweep + write protection
# ---------------------------------------------------------------------------


def _greedy(m, params, reqs, *, n_slots=3, max_seq=32, paged=True,
            page_tokens=8, **kw):
    if paged:
        eng = ServeEngine(m, params, n_slots=n_slots, max_seq=max_seq,
                          paged_kv=True, page_tokens=page_tokens, **kw)
    else:
        eng = ServeEngine(m, params, n_slots=n_slots, max_seq=max_seq)
    for r in reqs:
        eng.submit(Request(r.rid, r.prompt, r.max_new_tokens))
    out = {c.rid: c.tokens for c in eng.run()}
    return out, eng


@pytest.mark.parametrize("page_tokens", [4, 8])
@pytest.mark.parametrize("fork", ["full_pages", "partial_identical",
                                  "mid_page"])
def test_cow_shared_prefix_bit_identical(model_and_params, page_tokens, fork):
    """Property (satellite): COW-shared-prefix decode is bit-identical to
    the fully-materialized pool across fork points and page sizes — and to
    the dense engine (the paged parity sweep)."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(24)
    pt = page_tokens
    if fork == "full_pages":               # prefix ends on a page boundary
        pre = rng.randint(0, cfg.vocab, size=2 * pt)
        prompts = [np.concatenate([pre, rng.randint(0, cfg.vocab, size=3)]),
                   np.concatenate([pre, rng.randint(0, cfg.vocab, size=5)])]
    elif fork == "partial_identical":      # identical prompts: COW fork on
        p = rng.randint(0, cfg.vocab, size=2 * pt + 3)  # first decode write
        prompts = [p, p.copy()]
    else:                                  # prefix ends mid-page, tails differ
        pre = rng.randint(0, cfg.vocab, size=pt + 3)
        prompts = [np.concatenate([pre, rng.randint(0, cfg.vocab, size=4)]),
                   np.concatenate([pre, rng.randint(0, cfg.vocab, size=2)])]
    prompts.append(rng.randint(0, cfg.vocab, size=5))   # unrelated request
    reqs = [Request(i, p, 4) for i, p in enumerate(prompts)]
    shared, eng_s = _greedy(m, params, reqs, page_tokens=pt,
                            prefix_share=True)
    unshared, _ = _greedy(m, params, reqs, page_tokens=pt)
    assert shared == unshared
    st = eng_s.stats()
    assert st["pages_shared"] > 0
    if fork == "partial_identical":
        assert st["cow_copies"] >= 1       # the fork actually happened
    if page_tokens == 8:                   # dense parity leg of the sweep
        dense, _ = _greedy(m, params, reqs, paged=False)
        assert shared == dense


def test_cow_prefix_share_property_random(model_and_params):
    """Hypothesis variant of the bit-identity property: random prefix
    lengths (0..full prompt) and content seeds."""
    pytest.importorskip(
        "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    cfg, m, params = model_and_params
    PLEN = 11

    @settings(max_examples=4, deadline=None)
    @given(pre=st.integers(0, PLEN), seed=st.integers(0, 5),
           pt=st.sampled_from([4, 8]))
    def inner(pre, seed, pt):
        rng = np.random.RandomState(seed)
        prefix = rng.randint(0, cfg.vocab, size=pre)

        def mk(rid):
            tail = rng.randint(0, cfg.vocab, size=PLEN - pre)
            return Request(rid, np.concatenate([prefix, tail]).astype(np.int64), 3)

        reqs = [mk(0), mk(1)]
        shared, _ = _greedy(m, params, reqs, page_tokens=pt,
                            prefix_share=True)
        unshared, _ = _greedy(m, params, reqs, page_tokens=pt)
        assert shared == unshared

    inner()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_drops_writes_to_ro_pages(dtype):
    """A write-protected (shared) page must drop decode scatters aimed at
    it — like overflow writes — while the gather still reads it."""
    from repro.models import attention

    cfg = tiny_config("qwen3-4b")
    B, S, pt = 1, 8, 4
    params = attention.init_gqa(jax.random.PRNGKey(1), cfg)
    base = paginate_cache(attention.init_gqa_cache(cfg, B, S, dtype), pt)
    base = dict(base,
                page_table=base["page_table"].at[0].set(jnp.arange(S // pt)))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, 1, cfg.d_model), jnp.float32)
    positions = jnp.zeros((B, 1), jnp.int32)
    ro = dict(base, page_ro=base["page_ro"].at[0].set(True))
    _, new_ro = attention.gqa_attention(params, x, cfg, positions=positions,
                                        cache=ro)
    np.testing.assert_array_equal(np.asarray(new_ro["k_pages"]),
                                  np.asarray(base["k_pages"]))
    _, new_rw = attention.gqa_attention(params, x, cfg, positions=positions,
                                        cache=base)
    assert not np.array_equal(np.asarray(new_rw["k_pages"]),
                              np.asarray(base["k_pages"]))


def test_cow_sharing_admits_more_live_at_equal_pages(model_and_params):
    """The acceptance property: at equal physical page count, COW prefix
    sharing sustains strictly more concurrent sequences."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(25)
    prefix = rng.randint(0, cfg.vocab, size=16)   # 2 full pages at pt=8

    def live(share):
        eng = ServeEngine(m, params, n_slots=4, max_seq=32, paged_kv=True,
                          page_tokens=8, prefix_share=share, kv_pages=8)
        for rid in range(4):
            p = np.concatenate([prefix, rng.randint(0, cfg.vocab, size=4)])
            eng.submit(Request(rid, p, 6))
        done = eng.run()
        assert sorted(c.rid for c in done) == list(range(4))
        assert all(c.finished for c in done)
        return eng.stats()["max_live"]

    unshared = live(False)
    shared = live(True)
    assert shared > unshared
