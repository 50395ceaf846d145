"""The plain reference: DeepSeek-V2-Lite (arXiv:2405.04434) in
straightforward ``jax.numpy`` and float32 at the highest matmul precision,
at the configuration's expert share.  It imports nothing of the program
and reads the weights the benchmark made, by their names in the program's
layout.

``lowp=True`` is the control: the same arithmetic with every matmul's
operands rounded to float8 (e4m3), scaled per tensor for weights and per
row for activations -- the precision below the bfloat16 the configuration
states.

The block: RMSNorm, then multi-head latent attention in its expanded form
-- the query projected straight from x (no query LoRA); the latent
``c = RMSNorm(x W_dkv)``; per head K = [c W_uk, k_rope] and V = c W_uv,
with ``k_rope = rope(x W_kr)`` shared by the heads -- YaRN rotary
positions and the YaRN softmax temperature, causal softmax over key blocks;
then RMSNorm and the feed-forward: a SwiGLU in the first
``first_k_dense_replace`` layers, after them the routed experts (softmax
router over all the published experts, greedy top-k, gates not
renormalised) of which only the held ones add their gate-weighted output
-- no capacity, nothing dropped -- plus the shared experts as one SwiGLU.
A final RMSNorm and the untied head.

Departures from the published model: the rotary dims are rotated
half-split (the first half against the second) where the published code
de-interleaves them first; with random weights that is a fixed permutation
of the rope columns of ``W_q`` and ``W_kr``.  The experts not held here
add nothing (the configuration's expert share).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0
BLOCK = 1024          # keys per block of the causal softmax


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(eq, a, b, lowp, b_is_weight=True):
    if lowp:
        a = _fp8(a, -1)
        b = _fp8(b, None) if b_is_weight else _fp8(b, -1)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(cfg) -> np.ndarray:
    """The rotary frequencies under YaRN (the DeepSeek-V2 rule), float64."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    extra = 1.0 / theta ** (np.arange(0, d, 2) / d)
    inter = extra / y["factor"]

    def dim(rot):
        return d * math.log(y["original_max_position_embeddings"]
                            / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim(y["beta_fast"])), 0)
    hi = min(math.ceil(dim(y["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def softmax_scale(cfg) -> float:
    y = cfg["rope_scaling"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * _mscale(y["factor"], y["mscale_all_dim"]) ** 2


def _rope(x, cfg):
    """x (B, S, H, d_rope), half-split rotation at positions 0 .. S-1."""
    y = cfg["rope_scaling"]
    inv = jnp.asarray(yarn_inv_freq(cfg), F32)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    m = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"], y["mscale_all_dim"])
    cos = m * jnp.cos(ang)[None, :, None, :]
    sin = m * jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, a, cfg, lowp):
    eps, nope = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"]
    b, s, _ = h.shape
    q = _mm("bsd,dhk->bshk", h, a["w_q"], lowp)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    c = _rms(_mm("bsd,dr->bsr", h, a["w_dkv"], lowp), a["kv_norm"]["scale"], eps)
    kr = _rope(_mm("bsd,dr->bsr", h, a["w_kr"], lowp)[:, :, None, :], cfg)
    k_nope = _mm("bsr,rhk->bshk", c, a["w_uk"], lowp)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, k_nope.shape[:3] + kr.shape[-1:])], -1)
    v = _mm("bsr,rhv->bshv", c, a["w_uv"], lowp)
    if lowp:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    scale = softmax_scale(cfg)
    n = -(-s // BLOCK)
    pad = n * BLOCK - s
    kb = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, n, BLOCK, *k.shape[2:])
    vb = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, n, BLOCK, *v.shape[2:])
    qpos = jnp.arange(s)

    def step(carry, blk):
        mx, den, acc = carry
        kk, vv, i = blk
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HIGHEST) * scale
        causal = qpos[:, None] >= i * BLOCK + jnp.arange(BLOCK)[None, :]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        new = jnp.maximum(mx, sc.max(-1))
        p = jnp.exp(sc - new[..., None])
        fix = jnp.exp(mx - new)
        acc = acc * fix[..., None] + jnp.einsum("bhqk,bkhv->bhqv", p, vv,
                                                precision=HIGHEST)
        return (new, den * fix + p.sum(-1), acc), None

    heads, vd = v.shape[2], v.shape[3]
    init = (jnp.full((b, heads, s), -jnp.inf, F32), jnp.zeros((b, heads, s), F32),
            jnp.zeros((b, heads, s, vd), F32))
    (_, den, acc), _ = lax.scan(step, init, (jnp.moveaxis(kb, 1, 0),
                                             jnp.moveaxis(vb, 1, 0), jnp.arange(n)))
    o = jnp.moveaxis(acc / den[..., None], 1, 2)          # (b, s, h, v)
    return _mm("bshv,hvd->bsd", o, a["wo"], lowp)


def _swiglu(h, wi, wo, lowp):
    g, u = jnp.split(_mm("...d,df->...f", h, wi, lowp), 2, axis=-1)
    return _mm("...f,fd->...d", jax.nn.silu(g) * u, wo, lowp)


def _experts(h, m, cfg, lowp):
    """The held experts' gate-weighted sum plus the shared experts."""
    k = cfg["num_experts_per_tok"]
    first, stop = cfg["experts_held"]
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", h, m["router"],
                                      precision=HIGHEST), -1)
    gates, idx = lax.top_k(probs, k)
    out = _swiglu(h, m["shared"]["wi"], m["shared"]["wo"], lowp)
    for e in range(first, stop):
        w = jnp.where(idx == e, gates, 0.0).sum(-1)
        out = out + w[..., None] * _swiglu(h, m["wi"][e - first], m["wo"][e - first], lowp)
    return out


def _block(x, p, cfg, lowp, dense):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p["norm_mixer"]["scale"], eps), p["attn"], cfg, lowp)
    h = _rms(x, p["norm_ffn"]["scale"], eps)
    if dense:
        return x + _swiglu(h, p["mlp"]["wi"], p["mlp"]["wo"], lowp)
    return x + _experts(h, p["moe"], cfg, lowp)


def hidden(w, tokens, cfg, lowp=False):
    """Final-norm hidden states, (B, S, d)."""
    x = w["embed"]["table"][tokens].astype(F32)
    if lowp:
        x = _fp8(x, -1)
    for p in w["stack"]["prefix"]:
        x = _block(x, p, cfg, lowp, dense=True)

    def body(x, p):
        return _block(x, p, cfg, lowp, dense=False), None

    x, _ = lax.scan(body, x, w["stack"]["scan"]["l0"])
    return _rms(x, w["final_norm"]["scale"].astype(F32), cfg["rms_norm_eps"])


def logits(w, x, cfg, lowp=False):
    out = _mm("bsd,dv->bsv", x, w["lm_head"]["kernel"].astype(F32), lowp)
    return out[..., :cfg["vocab_size"]]


def served_gaps(w, tokens, at, served, cfg, lowp=False):
    """Per served position: how far the served token's logit lies below the
    reference's best, in standard deviations of that row.

    ``tokens`` (1, S) is the prompt followed by the served tokens (padded
    at the end; causal, so padding changes nothing before it); ``at`` (n,)
    the positions whose rows predict ``served`` (n,).  With ``lowp`` the
    gap is read for the token the float8 control puts first, against the
    float32 rows."""
    x = hidden(w, tokens, cfg)[0, at]
    rows = logits(w, x[None], cfg)[0]
    if lowp:
        xl = hidden(w, tokens, cfg, lowp=True)[0, at]
        served = jnp.argmax(logits(w, xl[None], cfg, lowp=True)[0], -1)
    best = rows.max(-1)
    pick = jnp.take_along_axis(rows, served[:, None], -1)[:, 0]
    return (best - pick) / rows.std(-1)
