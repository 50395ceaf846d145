"""DeepSeek-V2-Lite through the serving engine: the paged latent (MLA)
cache, YaRN rope and the expert share (``MoEConfig.experts_held``).

A tiny configuration of the V2-Lite shape -- no query LoRA, YaRN, a dense
first layer, 2 shared experts and 8 of 16 routed experts held -- runs
through ``ServeEngine(paged_kv=True)``, and its prefill and decode logits
are held to the plain reference's full forward pass
(``reference_mla.py``, a copy of ``bench/lib/reference_mla.py``) at every
step.  The expert share's parts add up to the uncut layer, the held path
drops nothing under skewed routing, and YaRN's frequencies and softmax
scale match hand-computed values.
"""
import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mla
from repro.configs import MLAConfig, get_config
from repro.configs.tiny import tiny_config
from repro.models import attention, build_model, layers, moe
from repro.serve.engine import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]


def tiny(held=(0, 8), n_experts=16, max_seq=64):
    cfg = get_config("deepseek-v2-lite")
    mo = dataclasses.replace(cfg.moe, num_experts=n_experts, top_k=6, d_ff_expert=32,
                             d_ff_shared=64, d_ff_first_dense=128, experts_held=held)
    return cfg.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                       d_ff=128, vocab=256, max_seq=max_seq, dtype="float32",
                       param_dtype="float32", moe=mo,
                       mla=MLAConfig(q_lora=0, kv_lora=32, qk_nope=16, qk_rope=8, v_head=16))


def published(mc) -> dict:
    """The reference's configuration (published key names) of ``mc``."""
    y = mc.rope_scaling
    return {"hidden_size": mc.d_model, "num_attention_heads": mc.n_heads,
            "qk_nope_head_dim": mc.mla.qk_nope, "qk_rope_head_dim": mc.mla.qk_rope,
            "v_head_dim": mc.mla.v_head, "kv_lora_rank": mc.mla.kv_lora,
            "rms_norm_eps": mc.norm_eps, "rope_theta": mc.rope_theta,
            "rope_scaling": {"type": "yarn", **dataclasses.asdict(y)},
            "num_experts_per_tok": mc.moe.top_k,
            "experts_held": list(mc.moe.experts_held), "vocab_size": mc.vocab,
            "first_k_dense_replace": mc.moe.first_dense}


def seeded_params(model, seed=0):
    """The program's init, with norm scales moved off 1 so the reference's
    reading of them is tested too."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, a):
        if getattr(path[-1], "key", None) == "scale":
            k = jax.random.fold_in(key, hash(jax.tree_util.keystr(path)) % 2**31)
            return a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(jitter, params)


def test_reference_is_the_benchmarks_copy():
    assert ((ROOT / "tests" / "reference_mla.py").read_text()
            == (ROOT / "bench" / "lib" / "reference_mla.py").read_text())


def _recorded(eng):
    """Wrap the executor's jitted calls to keep every logits row with the
    request and the tokens it had when the row was made."""
    ex, rows = eng.executor, []
    prefill, decode = ex._prefill_fn, ex._decode_fn

    def on_prefill(params, cache, tokens, *a):
        out = prefill(params, cache, tokens, *a)
        rows.append(("prefill", np.asarray(tokens[0]), np.asarray(out[0][0, -1])))
        return out

    def on_decode(params, cache, tokens):
        out = decode(params, cache, tokens)
        for slot, req in eng.slot_req.items():
            seq = np.concatenate([req.prompt, eng.slot_generated[slot]])
            rows.append(("decode", seq, np.asarray(out[0][slot, -1])))
        return out

    ex._prefill_fn, ex._decode_fn = on_prefill, on_decode
    return rows


@pytest.mark.parametrize("page_tokens,prompts", [
    (4, (13, 6)),     # prompts end inside a page; decode crosses pages
    (16, (21, 16)),   # one prompt crosses a page, one fills it exactly
    (8, (8, 30)),
])
def test_served_logits_match_the_reference(page_tokens, prompts):
    """Prefill into latent pages, then decode through them: every logits
    row agrees with the reference's full forward over the same tokens.

    Tolerance 2e-4 (absolute and relative, on logits of order 1): both
    sides compute in float32 (the reference at the highest matmul
    precision, as the CPU's float32 matmul is); they differ only in the
    order of summation -- online softmax over key blocks in prefill, the
    absorbed form in decode, the grouped expert tiles -- which moves
    float32 results by ~1e-6 relative per operation over 3 layers."""
    mc = tiny()
    model = build_model(mc)
    params = seeded_params(model)
    eng = ServeEngine(model, params, n_slots=2, max_seq=64, paged_kv=True,
                      page_tokens=page_tokens)
    rows = _recorded(eng)
    rng = np.random.default_rng(page_tokens)
    for rid, n in enumerate(prompts):
        eng.submit(Request(rid, rng.integers(0, mc.vocab, n).astype(np.int32), 7))
    done = eng.run(strict=True)
    assert sorted(len(c.tokens) for c in done) == [7, 7]
    cfg = published(mc)
    ref = jax.jit(lambda w, t: reference_mla.logits(
        w, reference_mla.hidden(w, t, cfg), cfg))
    assert sum(k == "decode" for k, _, _ in rows) == 12
    for kind, seq, got in rows:
        want = ref(params, jnp.asarray(seq)[None])[0, -1]
        np.testing.assert_allclose(got[:mc.vocab], np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=kind)


@pytest.mark.parametrize("mode", ["prefix_share", "tiered"])
def test_latent_pages_share_and_tier_as_gqa_pages(mode):
    """Copy-on-write prefix sharing and the host tier run on latent pages
    through the same engine paths as on GQA pages, and change no token."""
    mc = tiny()
    model = build_model(mc)
    params = seeded_params(model, 3)
    rng = np.random.default_rng(5)
    common = rng.integers(0, mc.vocab, 12).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(0, mc.vocab, n).astype(np.int32)])
               for n in (0, 5, 9)]

    def tokens(**kw):
        eng = ServeEngine(model, params, n_slots=2, max_seq=64, paged_kv=True,
                          page_tokens=4, **kw)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid, p, 6))
        out = {c.rid: c.tokens for c in eng.run(strict=True)}
        return out, eng.stats()

    want, _ = tokens()
    kw = {"prefix_share": True} if mode == "prefix_share" else {"kv_pages": (16, 16)}
    got, st = tokens(**kw)
    assert got == want
    if mode == "prefix_share":
        assert st["pages_shared"] > 0
    else:
        assert st["demotions"] > 0 and st["promotions"] > 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts held as 2 shares of 8: the routed parts of both shares,
    with the shared experts counted once, are the uncut layer."""
    full = tiny(held=None)
    p = moe.init_moe(jax.random.PRNGKey(1), full)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, full.d_model))
    want = moe.moe_ref(p, x, full)
    shared = layers.swiglu(x, p["shared"])
    total, held = -shared, 0
    for first in (0, 8):
        cfg = full.replace(moe=dataclasses.replace(full.moe, experts_held=(first, first + 8)))
        part = dict(p, wi=p["wi"][first:first + 8], wo=p["wo"][first:first + 8])
        out, _, counts = moe.moe_apply_held(part, x, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(moe.moe_ref(part, x, cfg)),
                                   rtol=1e-5, atol=1e-5)
        total, held = total + out, held + int(counts[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert held == 2 * 9 * full.moe.top_k      # every assignment, once


def test_held_path_drops_nothing_under_skewed_routing():
    """Every token routes to the same six held experts: the capacity
    dispatch drops most of them, the held path none."""
    cfg = tiny()
    p = moe.init_moe(jax.random.PRNGKey(4), cfg)
    bias = jnp.zeros(cfg.moe.num_experts).at[:6].set(jnp.arange(6, 0, -1) * 0.5)
    p["router"] = jnp.broadcast_to(bias, p["router"].shape)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))) + 0.1
    want = moe.moe_ref(p, x, cfg)
    out, _, counts = moe.moe_apply_held(p, x, cfg)
    assert counts.tolist() == [64 * 6, 6]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    # the same layer with all 16 experts (the 8 not held add zero) through
    # the capacity dispatch: 32 rows an expert, so half the assignments drop
    uncut = cfg.replace(moe=dataclasses.replace(cfg.moe, experts_held=None))
    pad = lambda w: jnp.concatenate([w, jnp.zeros_like(w)])
    dropped, _ = moe.moe_apply(dict(p, wi=pad(p["wi"]), wo=pad(p["wo"])), x, uncut)
    assert uncut.moe.capacity(64) == 32
    assert not np.allclose(np.asarray(dropped), np.asarray(want), atol=1e-3)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's YaRN: d 64, theta 1e4, factor 40 over 4096
    positions, beta 32 / 1: the ramp runs over pairs [10, 23]."""
    mc = get_config("deepseek-v2-lite")
    y = mc.rope_scaling
    assert layers.yarn_ramp_range(64, 1e4, y) == (10, 23)
    inv = np.asarray(layers.rope_frequencies(64, 1e4, y))
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)   # ramp 0
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    keep = 1 - 6 / 13                                              # pair 16
    assert inv[16] == pytest.approx(extra[16] * keep + extra[16] / 40 * (1 - keep),
                                    rel=1e-6)
    np.testing.assert_allclose(inv, reference_mla.yarn_inv_freq(published(tiny()) | {
        "qk_rope_head_dim": 64}), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1                              # 1.2608
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert attention.mla_softmax_scale(mc) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert attention.mla_softmax_scale(mc) == pytest.approx(0.114722, rel=1e-5)
    # the cos/sin scale is mscale(40, 0.707) / mscale(40, 0.707) = 1
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 1, 64))
    pos = jnp.arange(5)[None]
    plain = layers.apply_rope(x, pos, 1e4, dataclasses.replace(y, mscale=0.0,
                                                               mscale_all_dim=0.0))
    np.testing.assert_allclose(np.asarray(layers.apply_rope(x, pos, 1e4, y)),
                               np.asarray(plain), rtol=1e-6, atol=1e-6)


def test_counted_model_reports_its_experts_and_others_nothing_new():
    eng = ServeEngine(build_model(tiny()), seeded_params(build_model(tiny())),
                      n_slots=2, max_seq=64, paged_kv=True, page_tokens=8)
    eng.submit(Request(0, np.arange(10, dtype=np.int32), 4))
    eng.run(strict=True)
    st = eng.stats()
    assert st["prefill_calls"] == 1 and st["decode_calls"] == 3
    # 10 prompt tokens x 6 assignments x 2 expert layers, split by the share
    assert 0 < st["prefill_moe_held"] <= 10 * 6 * 2
    assert 0 < st["prefill_experts_hit"] <= 8 * 2
    # decode steps attend 11, 12, 13 keys: the prompt, the tokens so far
    assert st["decode_live_tokens"] == 11 + 12 + 13
    plain = tiny_config("deepseek-v2-236b")
    eng = ServeEngine(build_model(plain), build_model(plain).init(jax.random.PRNGKey(0)),
                      n_slots=2, max_seq=64, paged_kv=True, page_tokens=8)
    eng.submit(Request(0, np.arange(10, dtype=np.int32), 3))
    eng.run(strict=True)
    assert not eng.executor.counted
    assert not any("moe" in k or "live_tokens" in k for k in eng.stats())


def test_spans_carry_the_expert_counters(tmp_path):
    from jax.profiler import ProfileData

    mc = tiny()
    model = build_model(mc)
    eng = ServeEngine(model, seeded_params(model), n_slots=2, max_seq=64,
                      paged_kv=True, page_tokens=8)
    eng.submit(Request(0, np.arange(9, dtype=np.int32), 3))
    eng.run(strict=True)                     # compile outside the trace
    before = eng.stats()
    eng.submit(Request(1, np.arange(9, dtype=np.int32) + 1, 3))
    jax.profiler.start_trace(str(tmp_path))
    eng.run(strict=True)
    jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    spans = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name in ("serve.prefill", "serve.decode")]
    after = eng.stats()
    pre = [a for n, a in spans if n == "serve.prefill"]
    dec = [a for n, a in spans if n == "serve.decode"]
    assert len(pre) == 1 and len(dec) == 2
    assert [a["live_tokens"] for a in dec] == [10, 11]
    assert all(a["rows"] == 1 and 0 < a["experts_hit"] <= 16 for a in dec)
    for phase, got in (("prefill", pre), ("decode", dec)):
        for k in ("moe_held", "experts_hit"):
            key = f"{phase}_{k}"
            assert sum(a[k] for a in got) == after[key] - before[key]
