"""Cached GQA attention: grouped queries against the un-repeated KV.

The cache branch of ``gqa_attention`` takes its score operands in the
compute dtype and accumulates in f32.  A one-token query (every paged or
dense decode step) contracts each group of ``rep = H // KV`` query heads
against its one KV head; a multi-token query (prefill into a cache) keeps
the KV repeated to H heads.  These tests hold both to the formula they
replaced — KV repeated to all H heads and both score operands upcast to
f32 — and guard, on the jaxpr of a decode step, that neither the repeat
nor an f32 copy of the cache comes back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.tiny import tiny_config
from repro.models import attention, build_model
from repro.models.paged_cache import paginate_cache

B, S_MAX, PT, KV, HD = 2, 32, 4, 2, 8
POS = (5, 17)          # per-row lengths before the step: rows differ


def _attn_config(rep: int):
    """A GQA config whose q projection is the identity, so the test knows
    q exactly: d_model = H·hd, no bias, no qk-norm, no rotary."""
    H = KV * rep
    return tiny_config("starcoder2-3b").replace(
        d_model=H * HD, n_heads=H, n_kv_heads=KV, head_dim=HD,
        attn_bias=False, qk_norm=False, rope_theta=0.0)


def _identity_params(cfg, key):
    H, d = cfg.n_heads, cfg.d_model
    eye = jnp.eye(d, dtype=jnp.float32)
    params = attention.init_gqa(key, cfg)
    return dict(params, wq=eye.reshape(d, H, HD), wo=eye.reshape(H, HD, d))


def _reference(q, ck, cv, pos, dt):
    """The pre-grouping formula: KV repeated to every query head (the
    _expand_kv order, head h reads KV head h // rep), f32 scores."""
    _, S, H, hd = q.shape
    rep = H // ck.shape[2]
    kk = jnp.repeat(ck.astype(dt), rep, axis=2)
    vv = jnp.repeat(cv.astype(dt), rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * hd ** -0.5
    qpos = pos[:, None] + jnp.arange(S)[None, :]
    mask = qpos[:, None, :, None] >= jnp.arange(ck.shape[1])
    w = jax.nn.softmax(jnp.where(mask, scores, attention.NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(dt), vv)


def _cache(layout, dtype, rng):
    pos = jnp.asarray(POS, jnp.int32)
    k = jnp.asarray(rng.randn(B, S_MAX, KV, HD), dtype)
    v = jnp.asarray(rng.randn(B, S_MAX, KV, HD), dtype)
    if layout == "dense":
        return {"k": k, "v": v, "pos": pos}
    paged = paginate_cache({"k": k, "v": v, "pos": pos}, PT)
    n_alloc = B * S_MAX // PT
    parking = jnp.zeros_like(paged["k_pages"][-1:])
    perm = rng.permutation(n_alloc).astype(np.int32)
    table = perm.reshape(B, S_MAX // PT)
    ro = np.zeros(n_alloc + 1, bool)
    ro[table[1, POS[1] // PT]] = True      # row 1's write page: dropped
    hot = np.ones(n_alloc + 1, bool)
    hot[table[0, 0]] = False               # row 0's live prefix: parked
    hot[table[1, -1]] = False              # row 1's unwritten tail
    return dict(
        paged,
        k_pages=jnp.asarray(rng.randn(*paged["k_pages"].shape), dtype)
        .at[-1:].set(parking),
        v_pages=jnp.asarray(rng.randn(*paged["v_pages"].shape), dtype)
        .at[-1:].set(parking),
        page_table=jnp.asarray(table), page_ro=jnp.asarray(ro),
        page_hot=jnp.asarray(hot))


def _logical_kv(cache):
    """The (B, S_MAX, KV, hd) view a decode attends over, read from the
    cache after the step's scatter."""
    if "k" in cache:
        return cache["k"], cache["v"]
    parking = cache["k_pages"].shape[0] - 1
    hot = np.asarray(cache["page_hot"])
    table = np.asarray(cache["page_table"])
    table = np.where(hot[table], table, parking)
    return (cache["k_pages"][table].reshape(B, S_MAX, KV, HD),
            cache["v_pages"][table].reshape(B, S_MAX, KV, HD))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("S", [1, 4])
@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("rep", [1, 2, 12])
def test_grouped_cache_attention_matches_repeated_kv(rep, layout, S, dtype):
    cfg = _attn_config(rep)
    rng = np.random.RandomState(rep * 10 + S)
    params = _identity_params(cfg, jax.random.PRNGKey(rep))
    cache = _cache(layout, dtype, rng)
    x = jnp.asarray(rng.randn(B, S, cfg.d_model), dtype)
    positions = cache["pos"][:, None] + jnp.arange(S)[None, :]
    out, new = jax.jit(lambda x, c: attention.gqa_attention(
        params, x, cfg, positions=positions, cache=c))(x, cache)
    assert out.dtype == dtype
    assert new["pos"].tolist() == [p + S for p in POS]
    ck, cv = _logical_kv(new)
    q = x.reshape(B, S, cfg.n_heads, HD)
    ref = _reference(q, ck, cv, cache["pos"], dtype).reshape(out.shape)
    got = np.asarray(out, np.float32)
    want = np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        # both sides round the f32-accumulated output to bf16: a different
        # accumulation order moves an element by at most one bf16 step
        step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
        assert np.all(np.abs(got - want) <= step + 1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr, inside scans, loops and calls too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_paged_decode_jaxpr_has_no_kv_repeat_or_f32_cache_copy():
    """Structural guard of the grouped contraction: a paged decode step of
    a GQA model (rep = 4) broadcasts no cached KV to the H query heads and
    converts no (B, S_max, ·, hd) cache operand to f32."""
    cfg = tiny_config("starcoder2-3b", dtype="bfloat16").replace(
        n_heads=8, n_kv_heads=2, head_dim=16)
    H, hd = cfg.n_heads, cfg.head_dim
    assert H // cfg.n_kv_heads > 1
    m = build_model(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: paginate_cache(m.init_cache(B, S_MAX), PT))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(m.decode_step)(params, cache, tokens).jaxpr

    def kv_like(shape):
        return (len(shape) >= 4 and tuple(shape[:2]) == (B, S_MAX)
                and shape[-1] == hd)

    n_dots = 0
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "broadcast_in_dim":
            shape = eqn.outvars[0].aval.shape
            assert not (kv_like(shape) and np.prod(shape[2:-1]) == H), \
                f"KV repeated to {H} heads: {shape}"
        elif name == "convert_element_type":
            aval = eqn.invars[0].aval
            assert not (kv_like(aval.shape)
                        and eqn.params["new_dtype"] == jnp.float32), \
                f"f32 copy of a cache operand {aval.shape}"
        elif name == "dot_general":
            for v in eqn.invars:
                if kv_like(v.aval.shape):
                    assert v.aval.shape[2:] == (cfg.n_kv_heads, hd)
                    assert v.aval.dtype == jnp.bfloat16
                    n_dots += 1
    # scores and output, once each for the layers' shared body
    assert n_dots >= 2
