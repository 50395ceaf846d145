"""Transformer stack assembly: layer plans, scan-over-layers, enc-dec.

Every architecture reduces to a *layer plan* — a list of
:class:`LayerSpec` (mixer ∈ {gqa, mla, mamba} × ffn ∈ {dense, moe, none} ×
cross-attention flag).  The plan is decomposed into

    [prefix layers (unscanned)] + [repeating period × count (lax.scan)]

so that a 126-layer dense model scans one block, DeepSeek scans its 59 MoE
layers after one dense-FFN prefix layer, Llama4 scans a 2-layer
(dense, MoE) period, and Jamba scans its 8-layer (7 Mamba : 1 attention,
alternating MoE) period.  Scanning keeps the HLO size O(period), which is
what makes 512-device dry-run compiles tractable.

``remat="block"`` wraps each period application in ``jax.checkpoint``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, layers, moe as moe_lib, paged_cache, ssm
from repro.sharding import logical_constraint

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "gqa"   # gqa | mla | mamba
    ffn: str = "dense"   # dense | moe | none
    cross: bool = False  # add cross-attention (enc-dec decoder)


def layer_plan(cfg) -> list[LayerSpec]:
    """The per-layer structure of the decoder stack for ``cfg``."""
    plan = []
    for i in range(cfg.n_layers):
        if cfg.ssm is not None and cfg.hybrid_period:
            mixer = "gqa" if i % cfg.hybrid_period == cfg.hybrid_attn_offset else "mamba"
        elif cfg.ssm is not None:
            mixer = "mamba"
        elif cfg.mla is not None:
            mixer = "mla"
        else:
            mixer = "gqa"
        if cfg.family == "ssm":
            ffn = "none"  # pure Mamba2 blocks carry their own projections
        elif cfg.moe is not None:
            if i < cfg.moe.first_dense:
                ffn = "dense"
            elif i % cfg.moe.interleave_step == cfg.moe.interleave_offset:
                ffn = "moe"
            else:
                ffn = "dense"
        else:
            ffn = "dense"
        plan.append(LayerSpec(mixer=mixer, ffn=ffn,
                              cross=(cfg.enc_layers > 0)))
    return plan


def stage_plan(plan: list[LayerSpec]) -> tuple[int, int]:
    """Decompose ``plan`` into (prefix_len, period).  plan[prefix:] must be
    periodic with the returned period."""
    n = len(plan)
    for prefix in (0, 1, 2):
        rest = plan[prefix:]
        if not rest:
            continue
        for period in (1, 2, 4, 8, 16):
            if len(rest) % period == 0 and all(
                rest[i] == rest[i % period] for i in range(len(rest))
            ):
                return prefix, period
    return n, 1  # degenerate: everything unscanned


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def _norm_init(cfg):
    if cfg.norm == "layernorm":
        return layers.init_layernorm(cfg.d_model, cfg.param_dtype)
    return layers.init_rmsnorm(cfg.d_model, cfg.param_dtype)


def _norm_spec(cfg):
    return layers.layernorm_spec() if cfg.norm == "layernorm" else layers.rmsnorm_spec()


def _norm(x, p, cfg):
    if cfg.norm == "layernorm":
        return layers.layer_norm(x, p, cfg.norm_eps)
    return layers.rms_norm(x, p, cfg.norm_eps)


def init_block(key, spec: LayerSpec, cfg) -> dict:
    ks = jax.random.split(key, 6)
    p: dict = {"norm_mixer": _norm_init(cfg)}
    if spec.mixer == "gqa":
        p["attn"] = attention.init_gqa(ks[0], cfg)
    elif spec.mixer == "mla":
        p["attn"] = attention.init_mla(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = ssm.init_mamba2(ks[0], cfg)
    if spec.cross:
        p["norm_cross"] = _norm_init(cfg)
        p["cross"] = attention.init_gqa(ks[1], cfg)
    if spec.ffn == "dense":
        p["norm_ffn"] = _norm_init(cfg)
        d_ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.first_dense and cfg.moe.d_ff_first_dense:
            d_ff = cfg.moe.d_ff_first_dense
        if cfg.act == "gelu":
            p["mlp"] = layers.init_gelu_mlp(ks[2], cfg.d_model, d_ff, cfg.param_dtype,
                                            bias=cfg.attn_bias)
        else:
            p["mlp"] = layers.init_swiglu(ks[2], cfg.d_model, d_ff, cfg.param_dtype)
    elif spec.ffn == "moe":
        p["norm_ffn"] = _norm_init(cfg)
        p["moe"] = moe_lib.init_moe(ks[3], cfg)
    return p


def block_spec(spec: LayerSpec, cfg) -> dict:
    p: dict = {"norm_mixer": _norm_spec(cfg)}
    if spec.mixer == "gqa":
        p["attn"] = attention.gqa_spec(cfg)
    elif spec.mixer == "mla":
        p["attn"] = attention.mla_spec(cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = ssm.mamba2_spec(cfg)
    if spec.cross:
        p["norm_cross"] = _norm_spec(cfg)
        p["cross"] = attention.gqa_spec(cfg)
    if spec.ffn == "dense":
        p["norm_ffn"] = _norm_spec(cfg)
        p["mlp"] = (layers.gelu_mlp_spec(bias=cfg.attn_bias) if cfg.act == "gelu"
                    else layers.swiglu_spec())
    elif spec.ffn == "moe":
        p["norm_ffn"] = _norm_spec(cfg)
        p["moe"] = moe_lib.moe_spec(cfg)
    return p


def _counted(spec: LayerSpec, cfg) -> bool:
    """Whether the block's expert layer holds a share of the experts (and
    so counts what it routed to them, ``moe_apply_held``)."""
    return spec.ffn == "moe" and cfg.moe.experts_held is not None


def moe_counts(cache):
    """The sum over a cache tree's ``moe_counts`` leaves -- (assignments
    routed to held experts, held experts hit), each summed over the expert
    layers of the call that made the cache -- or ``None`` where it has
    none."""
    leaves = []

    def walk(t):
        if isinstance(t, dict):
            for key, val in t.items():
                if key == "moe_counts":
                    leaves.append(val.reshape(-1, 2).sum(0))
                else:
                    walk(val)
        elif isinstance(t, list):
            for val in t:
                walk(val)

    walk(cache)
    return sum(leaves[1:], leaves[0]) if leaves else None


def init_block_cache(spec: LayerSpec, cfg, batch: int, max_seq: int, dtype,
                     enc_len: int = 0) -> dict:
    c: dict = {}
    if _counted(spec, cfg):
        c["moe_counts"] = jnp.zeros((2,), jnp.int32)
    if spec.mixer == "gqa":
        c["attn"] = attention.init_gqa_cache(cfg, batch, max_seq, dtype)
    elif spec.mixer == "mla":
        c["attn"] = attention.init_mla_cache(cfg, batch, max_seq, dtype)
    elif spec.mixer == "mamba":
        c["mamba"] = ssm.init_mamba2_cache(cfg, batch, dtype)
    if spec.cross:
        c["cross"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        }
    return c


def block_cache_spec(spec: LayerSpec, cfg) -> dict:
    c: dict = {}
    if _counted(spec, cfg):
        c["moe_counts"] = (None,)
    if spec.mixer == "gqa":
        c["attn"] = attention.gqa_cache_spec(cfg)
    elif spec.mixer == "mla":
        c["attn"] = attention.mla_cache_spec(cfg)
    elif spec.mixer == "mamba":
        c["mamba"] = ssm.mamba2_cache_spec(cfg)
    if spec.cross:
        c["cross"] = {"k": ("batch", None, "kv_heads", None),
                      "v": ("batch", None, "kv_heads", None)}
    return c


def apply_block(
    params: dict,
    spec: LayerSpec,
    x: Array,
    cfg,
    *,
    positions: Array,
    cache: dict | None = None,
    enc_out: Array | None = None,
    causal: bool = True,
    cross_cached: bool = False,
):
    """One decoder block.  Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {} if cache is not None else None

    h = _norm(x, params["norm_mixer"], cfg)
    if spec.mixer in ("gqa", "mla"):
        fn = attention.gqa_attention if spec.mixer == "gqa" else attention.mla_attention
        sub = cache.get("attn") if cache is not None else None
        out, new_sub = fn(params["attn"], h, cfg, positions=positions, cache=sub,
                          **({"causal": causal, "block_kv": cfg.attn_block_kv}
                             if spec.mixer == "gqa" else {"block_kv": cfg.attn_block_kv}))
        if cache is not None:
            new_cache["attn"] = new_sub
    else:
        sub = cache.get("mamba") if cache is not None else None
        out, new_sub = ssm.mamba2_apply(params["mamba"], h, cfg, cache=sub)
        if cache is not None:
            new_cache["mamba"] = new_sub
    x = x + out

    if spec.cross:
        h = _norm(x, params["norm_cross"], cfg)
        sub = cache.get("cross") if cache is not None else None
        out, new_sub = attention.gqa_attention(
            params["cross"], h, cfg, positions=positions, cache=sub,
            causal=False, kv_input=enc_out if enc_out is not None else h,
            cross_cached=cross_cached)
        if cache is not None:
            new_cache["cross"] = new_sub
        x = x + out

    if spec.ffn != "none":
        h = _norm(x, params["norm_ffn"], cfg)
        if spec.ffn == "dense":
            out = (layers.gelu_mlp(h, params["mlp"]) if cfg.act == "gelu"
                   else layers.swiglu(h, params["mlp"]))
        elif _counted(spec, cfg):
            out, aux, counts = moe_lib.moe_apply_held(params["moe"], h, cfg)
            if cache is not None:
                new_cache["moe_counts"] = counts
        else:
            out, aux = moe_lib.moe_apply(params["moe"], h, cfg)
        x = x + out

    x = logical_constraint(x, "batch", "seq", "embed")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stack (prefix + scanned periods)
# ---------------------------------------------------------------------------


def init_stack(key, cfg, plan: list[LayerSpec] | None = None) -> dict:
    plan = plan if plan is not None else layer_plan(cfg)
    prefix, period = stage_plan(plan)
    count = (len(plan) - prefix) // period
    keys = jax.random.split(key, len(plan))
    params: dict = {"prefix": [init_block(keys[i], plan[i], cfg) for i in range(prefix)]}
    if count:
        per_layer = []
        for c in range(count):
            block = {
                f"l{j}": init_block(keys[prefix + c * period + j], plan[prefix + j], cfg)
                for j in range(period)
            }
            per_layer.append(block)
        params["scan"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    return params


def stack_spec(cfg, plan: list[LayerSpec] | None = None) -> dict:
    plan = plan if plan is not None else layer_plan(cfg)
    prefix, period = stage_plan(plan)
    count = (len(plan) - prefix) // period
    spec: dict = {"prefix": [block_spec(plan[i], cfg) for i in range(prefix)]}
    if count:
        blk = {f"l{j}": block_spec(plan[prefix + j], cfg) for j in range(period)}
        # scanned leaves get a leading "layers" (stacked) dim: prepend None
        spec["scan"] = jax.tree.map(
            lambda names: (None, *names), blk,
            is_leaf=lambda x: isinstance(x, tuple))
    return spec


def init_stack_cache(cfg, batch: int, max_seq: int, dtype, enc_len: int = 0,
                     plan=None) -> dict:
    plan = plan if plan is not None else layer_plan(cfg)
    prefix, period = stage_plan(plan)
    count = (len(plan) - prefix) // period
    cache: dict = {"step": jnp.zeros((batch,), jnp.int32), "prefix": [
        init_block_cache(plan[i], cfg, batch, max_seq, dtype, enc_len)
        for i in range(prefix)
    ]}
    if count:
        blk = {f"l{j}": init_block_cache(plan[prefix + j], cfg, batch, max_seq,
                                         dtype, enc_len) for j in range(period)}
        cache["scan"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (count,) + x.shape), blk)
    return cache


def stack_cache_spec(cfg, plan=None) -> dict:
    plan = plan if plan is not None else layer_plan(cfg)
    prefix, period = stage_plan(plan)
    count = (len(plan) - prefix) // period
    spec: dict = {"step": ("batch",), "prefix": [block_cache_spec(plan[i], cfg) for i in range(prefix)]}
    if count:
        blk = {f"l{j}": block_cache_spec(plan[prefix + j], cfg) for j in range(period)}
        spec["scan"] = jax.tree.map(lambda names: (None, *names), blk,
                                    is_leaf=lambda x: isinstance(x, tuple))
    return spec


def apply_stack(
    params: dict,
    x: Array,
    cfg,
    *,
    positions: Array,
    cache: dict | None = None,
    enc_out: Array | None = None,
    causal: bool = True,
    cross_cached: bool = False,
    plan: list[LayerSpec] | None = None,
):
    """Run the full stack.  Returns (x, new_cache, aux_loss_sum)."""
    plan = plan if plan is not None else layer_plan(cfg)
    prefix, period = stage_plan(plan)
    count = (len(plan) - prefix) // period
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = None
    if cache is not None:
        new_cache = {"step": cache["step"] + x.shape[1], "prefix": []}

    for i in range(prefix):
        sub = cache["prefix"][i] if cache is not None else None
        x, nc, aux = apply_block(params["prefix"][i], plan[i], x, cfg,
                                 positions=positions, cache=sub,
                                 enc_out=enc_out, causal=causal,
                                 cross_cached=cross_cached)
        aux_total = aux_total + aux
        if cache is not None:
            new_cache["prefix"].append(nc)

    if count:
        period_specs = [plan[prefix + j] for j in range(period)]

        def apply_period(x, aux, block_params, block_cache):
            ncache = {} if block_cache is not None else None
            for j, sp in enumerate(period_specs):
                sub = block_cache[f"l{j}"] if block_cache is not None else None
                x, nc, a = apply_block(block_params[f"l{j}"], sp, x, cfg,
                                       positions=positions, cache=sub,
                                       enc_out=enc_out, causal=causal,
                                       cross_cached=cross_cached)
                aux = aux + a
                if ncache is not None:
                    ncache[f"l{j}"] = nc
            return x, aux, ncache

        if cfg.remat == "block":
            apply_period = jax.checkpoint(
                apply_period, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=())

        if cache is not None:
            # a paged cache's stacked page pools ride the carry, and each
            # layer writes and reads them at its index, in place; the rest
            # of the cache is scanned as xs -> ys
            rest, pools = paged_cache.split_pools(cache["scan"])

            def body(carry, xs):
                xx, aux, pl = carry
                bp, bc, i = xs
                xx, aux, nc = apply_period(xx, aux, bp,
                                           paged_cache.join_pools(bc, pl, i))
                nc, pl = paged_cache.split_pools(nc)
                return (xx, aux, pl), nc
            (x, aux_total, pools), scanned_cache = lax.scan(
                body, (x, aux_total, pools),
                (params["scan"], rest, jnp.arange(count)))
            new_cache["scan"] = paged_cache.join_pools(scanned_cache, pools)
        else:
            def body(carry, bp):
                xx, aux = carry
                xx, aux, _ = apply_period(xx, aux, bp, None)
                return (xx, aux), None
            (x, aux_total), _ = lax.scan(body, (x, aux_total), params["scan"])

    return x, new_cache, aux_total


__all__ = [
    "LayerSpec", "layer_plan", "stage_plan",
    "init_block", "block_spec", "apply_block",
    "init_block_cache", "block_cache_spec",
    "init_stack", "stack_spec", "apply_stack",
    "init_stack_cache", "stack_cache_spec", "moe_counts",
]
