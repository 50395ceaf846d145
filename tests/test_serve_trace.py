"""The serving engine's tracing surface: ``serve.*`` host spans in the
profiler's trace, the count of KV pages holding tokens, each request's
host times and the prefill-shape count in ``stats()``."""
import glob
import math

import jax
import numpy as np
import pytest

from repro.configs.tiny import tiny_config
from repro.models import build_model
from repro.serve import engine as engine_mod
from repro.serve.engine import Request, ServeEngine

PAGE = 8
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    cfg = tiny_config("qwen3-4b")
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _requests(cfg, n=6, seed=0):
    """Prompts of 3-10 tokens (crossing page edges), 1-6 new tokens; rid 2
    ends at its prefill token."""
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=3 + (i * 3) % 8),
                    max_new_tokens=1 if i == 2 else 2 + i % 5)
            for i in range(n)]


def _engine(model_and_params, **kw):
    _, m, params = model_and_params
    return ServeEngine(m, params, n_slots=2, max_seq=MAX_SEQ, paged_kv=True,
                       page_tokens=PAGE, **kw)


def _host_spans(path):
    """[(name, start_ns, end_ns, args)] of every ``serve.*`` host event."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_is_a_shared_no_op_while_no_trace_runs():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert engine_mod._span("serve.step", tick=1) is engine_mod._NO_SPAN


def test_engine_emits_the_serve_span_tree_with_arguments(model_and_params,
                                                         tmp_path):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    eng.step()                       # compiles outside the trace
    done_before = len(eng.done)
    late = Request(rid=len(reqs), prompt=reqs[0].prompt, max_new_tokens=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(late)
        ticks = 0
        while eng.scheduler.pending_count or eng.slot_req:
            eng.step()
            ticks += 1
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = _host_spans(path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["serve.step"]
    assert len(steps) == ticks
    assert [s[3]["tick"] for s in steps] == list(range(1, ticks + 1))
    for s in steps:
        assert set(s[3]) == {"tick", "live", "queued", "pages_reserved",
                             "pages_used"}
        assert 0 <= s[3]["pages_used"] <= s[3]["pages_reserved"]
    # every request but the two the untraced first tick took is prefilled
    # in the trace, once, with its own arguments
    pre = by["serve.prefill"]
    traced = {s[3]["rid"] for s in pre}
    assert traced == {r.rid for r in reqs + [late]} - {0, 1}
    for s in pre:
        r = (reqs + [late])[s[3]["rid"]]
        assert set(s[3]) == {"rid", "prompt_len", "slot"}
        assert s[3]["prompt_len"] == len(r.prompt)
        assert s[3]["slot"] in (0, 1)
    # the one submission in the trace, before its prefill
    (sub,) = by["serve.submit"]
    assert sub[3] == {"rid": late.rid}
    assert sub[2] <= next(s[1] for s in pre if s[3]["rid"] == late.rid)
    # nesting: prefill.sync in prefill, decode.sync in decode, all in a step
    for name, parent in (("serve.prefill.sync", "serve.prefill"),
                         ("serve.decode.sync", "serve.decode"),
                         ("serve.prefill", "serve.admit"),
                         ("serve.admit", "serve.step"),
                         ("serve.decode", "serve.step"),
                         ("serve.commit", "serve.step"),
                         ("serve.release", "serve.step")):
        assert by[name], name
        for s in by[name]:
            assert any(_inside(s, p) for p in by[parent]), (name, parent)
    assert len(by["serve.prefill.sync"]) == len(pre)
    assert len(by["serve.decode.sync"]) == len(by["serve.decode"])
    # the slot teardown of every request finished in the trace, with the
    # pages it held
    assert len(by["serve.release"]) == len(eng.done) - done_before
    assert all(s[3]["pages"] == MAX_SEQ // PAGE for s in by["serve.release"])


def test_pages_used_is_the_pages_holding_tokens_after_each_tick(
        model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    for r in _requests(cfg, n=8, seed=1):
        eng.submit(r)
    sums = [0, 0]
    while eng.scheduler.pending_count or eng.slot_req:
        st = eng.stats()
        sums[0] += eng.pool.n_pages - eng.pool.n_free
        sums[1] += st["pages_used"]
        eng.step()
        held = sum(math.ceil((eng.slot_pos[s] - 1) / PAGE) for s in eng.slot_req)
        assert eng.stats()["pages_used"] == held
    st = eng.stats()
    assert st["pages_used"] == 0
    assert (st["tick_pages_reserved"], st["tick_pages_used"]) == tuple(sums)
    assert 0 < st["tick_pages_used"] < st["tick_pages_reserved"]


@pytest.mark.parametrize("kv_pages", [None, (8, 16)], ids=["hbm", "tiered"])
def test_pages_used_counts_a_shared_page_once_and_hbm_pages_only(
        model_and_params, kv_pages):
    """With prefix sharing a page two slots hold counts once; with a cold
    tier only the hot slots' HBM pages count: never above the reserved."""
    cfg, m, params = model_and_params
    rng = np.random.RandomState(7)
    base = rng.randint(0, cfg.vocab, size=16)
    eng = ServeEngine(m, params, n_slots=4, max_seq=64, paged_kv=True,
                      page_tokens=16, prefix_share=True, kv_pages=kv_pages)
    for i in range(4):
        tail = rng.randint(0, cfg.vocab, size=3 * i)
        eng.submit(Request(rid=i, prompt=np.concatenate([base, tail]),
                           max_new_tokens=5))
    shared_seen = False
    while eng.scheduler.pending_count or eng.slot_req:
        eng.step()
        held = {p for s, pages in eng.slot_pages.items()
                for p in pages[:math.ceil((eng.slot_pos[s] - 1) / 16)]}
        per_slot = sum(math.ceil((eng.slot_pos[s] - 1) / 16)
                       for s in eng.slot_pages)
        shared_seen |= len(held) < per_slot
        assert eng.stats()["pages_used"] == len(held)
        assert len(held) <= eng.pool.n_pages - eng.pool.n_free
    assert shared_seen
    if kv_pages:
        assert eng.stats()["demotions"] > 0


def test_completion_host_times_are_ordered(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    reqs = _requests(cfg, n=7, seed=2)
    for r in reqs:
        eng.submit(r)
    done = eng.run(strict=True)
    assert sorted(c.rid for c in done) == [r.rid for r in reqs]
    for c in done:
        assert 0 < c.t_submit <= c.t_admit <= c.t_first <= c.t_out, c


def test_partial_completions_carry_the_times_reached(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    for r in _requests(cfg, n=5, seed=3):
        eng.submit(r)
    out = {c.rid: c for c in eng.run(max_ticks=1)}
    live = [c for c in out.values() if not c.finished and c.tokens]
    queued = [c for c in out.values() if not c.tokens]
    assert live and queued
    for c in live:
        assert 0 < c.t_submit <= c.t_admit <= c.t_first <= c.t_out
    for c in queued:
        assert c.t_submit > 0 and c.t_admit == c.t_first == c.t_out == 0.0


def test_prefill_shapes_counts_distinct_prompt_lengths(model_and_params):
    cfg = model_and_params[0]
    eng = _engine(model_and_params)
    lengths = [5, 9, 5, 12, 9, 5]
    rng = np.random.RandomState(4)
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=n),
                           max_new_tokens=2))
    assert eng.stats()["prefill_shapes"] == 0
    eng.run(strict=True)
    assert eng.stats()["prefill_shapes"] == len(set(lengths))
