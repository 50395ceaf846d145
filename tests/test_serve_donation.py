"""The executor's cache is donated: decode and prefill write the page pools
in place, and the decode layer scan carries them.

Tiny paged engines -- GQA, and latent attention (MLA) with an expert share
-- must alias every pool byte in the decode program the executor runs and
in its prefill, and JAX must never find a donated buffer unusable.  A run
through prefix sharing (a copy-on-write fork), the host tier (demote and
promote) and slot release (park) must serve the tokens the unpaged engine
serves: a use of the cache after it was donated raises.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MLAConfig, get_config
from repro.configs.tiny import tiny_config
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine

MAX_SEQ = 64


def _mla_config():
    cfg = get_config("deepseek-v2-lite")
    mo = dataclasses.replace(cfg.moe, num_experts=16, top_k=6, d_ff_expert=32,
                             d_ff_shared=64, d_ff_first_dense=128,
                             experts_held=(0, 8))
    return cfg.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                       head_dim=16, d_ff=128, vocab=256, max_seq=MAX_SEQ,
                       dtype="float32", param_dtype="float32", moe=mo,
                       mla=MLAConfig(q_lora=0, kv_lora=32, qk_nope=16,
                                     qk_rope=8, v_head=16))


CONFIGS = {"gqa": lambda: tiny_config("qwen3-4b"), "mla": _mla_config}


@pytest.fixture(scope="module")
def models():
    out = {}
    for name, make in CONFIGS.items():
        cfg = make()
        model = build_model(cfg)
        out[name] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return out


def _served(eng, prompts, max_new=6):
    """Tokens of every request, run with an unusable donation an error."""
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid, p, max_new))
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="Some donated buffers were not usable")
        return {c.rid: c.tokens for c in eng.run(strict=True)}


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_decode_and_prefill_alias_the_pools(models, arch):
    cfg, model, params = models[arch]
    eng = ServeEngine(model, params, n_slots=2, max_seq=MAX_SEQ, paged_kv=True,
                      page_tokens=16)
    rng = np.random.default_rng(1)
    _served(eng, [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (5, 20)])
    st = eng.stats()
    assert st["pool_bytes"] > 0
    assert st["decode_pool_aliased_bytes"] >= st["pool_bytes"]
    ex = eng.executor
    pages = MAX_SEQ // 16
    prefill = ex._prefill_fn.lower(
        params, ex.cache, jnp.zeros((1, 5), jnp.int32), 0,
        jnp.arange(pages, dtype=jnp.int32), jnp.ones(pages, bool)).compile()
    assert prefill.memory_analysis().alias_size_in_bytes >= st["pool_bytes"]


def test_the_decode_scan_carries_the_stacked_pools(models):
    """In the decode program the layer scan carries every scanned pool
    whole, and nothing slices a layer's pool out of it."""
    _, model, params = models["gqa"]
    eng = ServeEngine(model, params, n_slots=2, max_seq=MAX_SEQ, paged_kv=True,
                      page_tokens=16)
    ex = eng.executor
    pools = {x.shape for path, x in jax.tree_util.tree_leaves_with_path(ex.cache["scan"])
             if jax.tree_util.keystr(path).endswith("_pages']")}
    assert pools
    jaxpr = jax.make_jaxpr(ex._decode_jit)(params, ex.cache,
                                           jnp.zeros((2, 1), jnp.int32))
    scans, sliced = [], []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
                scans.append({v.aval.shape for v in
                              eqn.invars[n_consts:n_consts + n_carry]})
            if eqn.primitive.name in ("dynamic_slice", "slice"):
                if eqn.invars[0].aval.shape in pools:
                    sliced.append(eqn)
            for val in eqn.params.values():
                for sub in val if isinstance(val, (list, tuple)) else [val]:
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    assert any(pools <= carried for carried in scans), scans
    assert not sliced


@pytest.mark.parametrize("mode", ["prefix_share", "tiered"])
@pytest.mark.parametrize("arch,page_tokens", [("gqa", 4), ("mla", 4), ("mla", 16)])
def test_pool_lifecycle_on_donated_caches_serves_unpaged_tokens(
        models, arch, page_tokens, mode):
    """COW forks, tier demotion and promotion, and parking on release all
    rewrite the cache between donated calls; the tokens stay the unpaged
    engine's.  With 16-token pages the latent rope key (8 wide) is stored
    16 tokens to a row of 128 lanes."""
    cfg, model, params = models[arch]
    rng = np.random.default_rng(page_tokens)
    common = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(0, cfg.vocab, 7).astype(np.int32)]),
               common,                     # ends mid-page: a writable share
               rng.integers(0, cfg.vocab, 9).astype(np.int32),
               np.concatenate([common, rng.integers(0, cfg.vocab, 3).astype(np.int32)]),
               rng.integers(0, cfg.vocab, 21).astype(np.int32)]
    pps = MAX_SEQ // page_tokens
    kw = ({"prefix_share": True} if mode == "prefix_share"
          else {"kv_pages": (pps, pps)})
    want = _served(ServeEngine(model, params, n_slots=2, max_seq=MAX_SEQ), prompts)
    eng = ServeEngine(model, params, n_slots=2, max_seq=MAX_SEQ, paged_kv=True,
                      page_tokens=page_tokens, **kw)
    got = _served(eng, prompts)
    assert got == want
    st = eng.stats()
    assert st["pages_freed"] > 0                      # released, parked
    if mode == "prefix_share":
        assert st["cow_copies"] > 0
    else:
        assert st["demotions"] > 0 and st["promotions"] > 0
