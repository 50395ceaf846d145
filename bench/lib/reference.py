"""The plain reference: StarCoder2 (arXiv:2402.19173) in straightforward
``jax.numpy`` and float32 at the highest matmul precision, with its loss,
its gradients and AdamW.  It imports nothing of the program and reads the
weights the benchmark made, by their names in the program's layout.

``lowp=True`` is the control: the same arithmetic with every matmul's
operands rounded to float8 (e4m3), scaled per tensor for weights and per
row for activations -- the precision below the bfloat16 the configurations
state -- and the gradient taken through the rounding unchanged.

The block: pre-LayerNorm (with bias), grouped-query attention with biases
and rotary positions (half-split, theta from the configuration), causal
softmax, an MLP with biases and tanh-GELU, a final LayerNorm, and the
head (the embedding, transposed, where the configuration ties them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


# The gradient passes the rounding unchanged, as in float8 training, where
# the float32 master weights take the gradient taken at the rounded ones.
# (Differentiating the casts would round the gradients themselves to
# e4m3 with no scale, flushing most of them to zero.)
_fp8.defvjp(lambda x, axis: (_fp8(x, axis), None), lambda axis, _, g: (g,))


def _mm(eq, a, b, lowp, b_is_weight=True):
    if lowp:
        a = _fp8(a, -1)
        b = _fp8(b, None) if b_is_weight else _fp8(b, -1)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, p, cfg, lowp):
    p = jax.tree.map(lambda a: a.astype(F32), p)
    eps, heads, kv = cfg["norm_epsilon"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = p["attn"]
    h = _ln(x, p["norm_mixer"], eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], lowp) + a["bq"]
    k = _mm("bsd,dhk->bshk", h, a["wk"], lowp) + a["bk"]
    v = _mm("bsd,dhk->bshk", h, a["wv"], lowp) + a["bv"]
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, lowp, False) / np.sqrt(q.shape[-1])
    n = x.shape[1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _fp8(w, -1) if lowp else w,
                   _fp8(v, -1) if lowp else v, precision=HIGHEST)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], lowp) + a["bo"]
    m = p["mlp"]
    h = _ln(x, p["norm_ffn"], eps)
    h = jax.nn.gelu(_mm("bsd,df->bsf", h, m["wi"], lowp) + m["bi"], approximate=True)
    return x + _mm("bsf,fd->bsd", h, m["wo"], lowp) + m["bo"]


def hidden(w, tokens, cfg, lowp=False):
    """Final-norm hidden states, (B, S, d)."""
    x = w["embed"]["table"][tokens].astype(F32)
    if lowp:
        x = _fp8(x, -1)

    def body(x, p):
        return _block(x, p, cfg, lowp), None

    x, _ = lax.scan(body, x, w["stack"]["scan"]["l0"])
    fn = jax.tree.map(lambda a: a.astype(F32), w["final_norm"])
    return _ln(x, fn, cfg["norm_epsilon"])


def logits(w, x, cfg, lowp=False):
    if cfg["tie_word_embeddings"]:
        out = _mm("bsd,vd->bsv", x, w["embed"]["table"].astype(F32), lowp)
    else:
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


# -- training ----------------------------------------------------------------

def loss(w, tokens, labels, cfg, lowp=False):
    """Mean next-token cross-entropy over every position."""
    lg = logits(w, hidden(w, tokens, cfg, lowp), cfg, lowp)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return -ll.mean()


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr``."""
    if step < opt["warmup_steps"]:
        return opt["peak_lr"] * step / max(1, opt["warmup_steps"])
    frac = min(max((step - opt["warmup_steps"])
                   / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0), 1.0)
    return opt["min_lr"] + 0.5 * (opt["peak_lr"] - opt["min_lr"]) * (1 + np.cos(np.pi * frac))


def adamw(params, grads, m, v, lr, c1, c2, opt: dict):
    """One AdamW step after clipping the global gradient norm; ``lr`` and
    the bias corrections ``c1``, ``c2`` are the step's."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, a, b):
        return p - lr * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                         + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


def train(params, batches, cfg, opt, *, rows: int, lowp=False):
    """``len(batches)`` AdamW steps from ``params`` (float32), the gradient
    of each taken ``rows`` sequences at a time.  Returns the losses, the
    first step's clipped gradient (as the optimizer gets it) and the
    parameters after the last step."""
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, t, l, cfg, lowp)))
    step_fn = jax.jit(adamw, static_argnums=(7,))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches, start=1):
        n = tokens.shape[0] // rows
        tot, acc = 0.0, None
        for b in range(n):
            sl = slice(b * rows, (b + 1) * rows)
            l, g = vg(params, tokens[sl], labels[sl])
            tot = tot + l
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        grads = jax.tree.map(lambda g: g / n, acc)
        losses.append(float(tot) / n)
        params, m, v, clipped = step_fn(
            params, grads, m, v, F32(lr_at(opt, i)), F32(1 - opt["b1"] ** i),
            F32(1 - opt["b2"] ** i), _Frozen(opt))
        if first is None:
            first = clipped
    return losses, first, params


class _Frozen(dict):
    """A hashable optimizer dict (a static argument of the jitted step)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
