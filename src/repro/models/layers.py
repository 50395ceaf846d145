"""Basic neural layers in pure JAX (no flax): norms, embeddings, MLPs, RoPE.

Conventions used across the model zoo:

* Parameters are nested dicts of ``jax.Array``; every ``init_*`` function has
  a ``*_spec`` twin returning an identically-structured tree of *logical axis
  name tuples* (one entry per array dim, ``None`` = replicated).  The
  distribution layer maps logical names to mesh axes (``repro.launch.sharding``).
* ``cfg.dtype`` is the activation/compute dtype (bf16 for production shapes);
  ``cfg.param_dtype`` the parameter storage dtype.
* All apply functions are pure.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, scale: float, dtype) -> Array:
    """He/fan-in style truncated-normal initializer."""
    stddev = scale / np.sqrt(max(1, shape[0] if len(shape) else 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, *, scale: float = 1.0) -> Array:
    return trunc_normal(key, (d_in, d_out), scale, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_spec() -> dict:
    return {"scale": ("embed",)}


def rms_norm(x: Array, params: dict, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_spec() -> dict:
    return {"scale": ("embed",), "bias": ("embed",)}


def layer_norm(x: Array, params: dict, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(key, vocab: int, d: int, dtype) -> dict:
    return {"table": trunc_normal(key, (vocab, d), 1.0, dtype)}


def embed_spec() -> dict:
    return {"table": ("vocab", "embed")}


def embed(x_tokens: Array, params: dict, dtype) -> Array:
    return params["table"].astype(dtype)[x_tokens]


def unembed(x: Array, params: dict) -> Array:
    """Project to vocab logits (fp32 for a stable softmax/loss)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))


def init_lm_head(key, d: int, vocab: int, dtype) -> dict:
    return {"kernel": dense_init(key, d, vocab, dtype)}


def lm_head_spec() -> dict:
    return {"kernel": ("embed", "vocab")}


def lm_head(x: Array, params: dict) -> Array:
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                      params["kernel"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(key, d: int, ff: int, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    # fused gate+up projection: better for tensor parallelism (one matmul)
    return {
        "wi": dense_init(k1, d, 2 * ff, dtype),
        "wo": dense_init(k2, ff, d, dtype),
    }


def swiglu_spec() -> dict:
    return {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}


def swiglu(x: Array, params: dict) -> Array:
    dtype = x.dtype
    h = jnp.einsum("...d,df->...f", x, params["wi"].astype(dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(dtype))


def init_gelu_mlp(key, d: int, ff: int, dtype, *, bias: bool = True) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"wi": dense_init(k1, d, ff, dtype), "wo": dense_init(k2, ff, d, dtype)}
    if bias:
        p["bi"] = jnp.zeros((ff,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def gelu_mlp_spec(*, bias: bool = True) -> dict:
    p = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if bias:
        p["bi"] = ("mlp",)
        p["bo"] = ("embed",)
    return p


def gelu_mlp(x: Array, params: dict) -> Array:
    dtype = x.dtype
    h = jnp.einsum("...d,df->...f", x, params["wi"].astype(dtype))
    if "bi" in params:
        h = h + params["bi"].astype(dtype)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(dtype)
    out = jnp.einsum("...f,fd->...d", h, params["wo"].astype(dtype))
    if "bo" in params:
        out = out + params["bo"].astype(dtype)
    return out


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp_range(head_dim: int, theta: float, yarn) -> tuple[int, int]:
    """The dim pairs over which YaRN blends the interpolated frequencies
    into the original ones: ``[floor(fcd(beta_fast)), ceil(fcd(beta_slow))]``
    with ``fcd(r) = d·ln(L0 / (2π·r)) / (2·ln θ)``, clipped to the dims."""
    L0 = yarn.original_max_position_embeddings

    def fcd(rotations):
        return (head_dim * math.log(L0 / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(fcd(yarn.beta_fast)), 0)
    hi = min(math.ceil(fcd(yarn.beta_slow)), head_dim - 1)
    return lo, hi


def rope_frequencies(head_dim: int, theta: float, yarn=None) -> Array:
    """Inverse frequencies for RoPE (fp32).  With ``yarn`` (a
    :class:`repro.configs.base.YarnConfig`), the DeepSeek-V2 YaRN rule:
    pairs below the ramp keep their frequency, pairs above it are divided
    by ``factor``, and the ramp blends the two linearly."""
    exponents = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    inv = 1.0 / (theta ** exponents)
    if yarn is not None:
        lo, hi = yarn_ramp_range(head_dim, theta, yarn)
        ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - lo)
                       / max(hi - lo, 1e-3), 0.0, 1.0)
        keep = 1.0 - ramp
        inv = inv * keep + inv / yarn.factor * (1.0 - keep)
    return jnp.asarray(inv, jnp.float32)


def apply_rope(x: Array, positions: Array, theta: float, yarn=None) -> Array:
    """Rotate ``x`` (..., seq, heads, head_dim) by position-dependent angles.

    ``positions``: (..., seq) int32 absolute positions (decode passes the
    cache offset).  Uses the half-split convention (LLaMA/NeoX style).
    ``yarn`` scales the frequencies (:func:`rope_frequencies`) and the
    cos/sin by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    """
    *_, seq, heads, hd = x.shape
    inv = rope_frequencies(hd, theta, yarn)  # (hd/2,)
    angles = positions.astype(jnp.float32)[..., :, None] * inv[None, :]  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# learned absolute positions (whisper-style)
# ---------------------------------------------------------------------------


def init_learned_pos(key, max_len: int, d: int, dtype) -> dict:
    return {"pos": trunc_normal(key, (max_len, d), 0.02 * np.sqrt(max_len), dtype)}


def learned_pos_spec() -> dict:
    return {"pos": (None, "embed")}


def add_learned_pos(x: Array, params: dict, offset=0) -> Array:
    seq = x.shape[-2]
    pos = jax.lax.dynamic_slice_in_dim(params["pos"], offset, seq, axis=0)
    return x + pos.astype(x.dtype)


__all__ = [
    "trunc_normal", "dense_init",
    "init_rmsnorm", "rmsnorm_spec", "rms_norm",
    "init_layernorm", "layernorm_spec", "layer_norm",
    "init_embed", "embed_spec", "embed", "unembed",
    "init_lm_head", "lm_head_spec", "lm_head",
    "init_swiglu", "swiglu_spec", "swiglu",
    "init_gelu_mlp", "gelu_mlp_spec", "gelu_mlp",
    "yarn_mscale", "yarn_ramp_range", "rope_frequencies", "apply_rope",
    "init_learned_pos", "learned_pos_spec", "add_learned_pos",
]
