"""Reduced same-family configs for smoke tests, examples and CI.

``tiny_config(arch)`` keeps the *structure* of the assigned architecture
(family, mixer types, MoE interleave, hybrid period, enc-dec, qk-norm, ...)
while shrinking widths/layers/experts so a forward+train step runs on one CPU
in seconds.  The FULL configs are exercised only via the dry-run
(ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, get_config


def tiny_config(arch: str, *, dtype: str = "float32") -> ModelConfig:
    cfg = get_config(arch)
    kw: dict = dict(
        d_model=64, d_ff=128, vocab=256, max_seq=256,
        dtype=dtype, param_dtype="float32",
        n_layers=cfg.hybrid_period if cfg.hybrid_period else 2,
    )
    if cfg.n_heads > 1:
        kw.update(n_heads=4, n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads else 4,
                  head_dim=16)
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora=32 if cfg.mla.q_lora else 0, kv_lora=32,
                              qk_nope=16, qk_rope=8, v_head=16)
        kw.update(n_heads=4, n_kv_heads=4, head_dim=16)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, top_k=min(2, cfg.moe.top_k),
            d_ff_expert=64, d_ff_shared=64, d_ff_first_dense=128,
            first_dense=min(1, cfg.moe.first_dense),
            capacity_factor=8.0,  # ample: no drops, so oracles match exactly
        )
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, headdim=16, chunk=8)
    if cfg.enc_layers:
        kw["enc_layers"] = 2
    if cfg.vlm_prefix:
        kw["vlm_prefix"] = 4
    return cfg.replace(**kw)


__all__ = ["tiny_config"]
