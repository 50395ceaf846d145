"""Operations and bytes of a DeepSeek-V2 configuration's calls (latent
attention, routed experts at an expert share), from the configuration's
shapes and the engine's counters.

Counts are of the work the algorithm needs: causal attention reads only
the positions before the query, in whichever MLA form is cheaper (the
expanded one in a prefill, the absorbed one in a decode step); a decode
step reads the latents of live positions only and, of the routed experts,
only the held ones its rows chose (``experts_hit``); routed operations
are those of the assignments the held experts took (``moe_held``).
Sizes are in the published config's names.
"""
from __future__ import annotations

ITEM = 2      # bytes of a bfloat16 weight or latent


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vh, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    moe_ff = cfg["moe_intermediate_size"]
    attn = d * h * (nope + rope) + d * r + d * rope + r * h * (nope + vh) + h * vh * d
    return {
        "d": d, "h": h, "nope": nope, "rope": rope, "vh": vh, "r": r,
        "layers": layers, "moe_layers": layers - dense,
        "attn": attn,
        "dense_ffn": 3 * d * cfg["intermediate_size"],
        "shared": 3 * d * cfg["n_shared_experts"] * moe_ff,
        "router": d * cfg["published"]["n_routed_experts"],
        "expert": 3 * d * moe_ff,
        "head": d * cfg["vocab_size"],
        "dense_layers": dense,
    }


def token_params(cfg: dict) -> int:
    """Weights every token passes through, outside the routed experts and
    the head: attention, the dense layers' FFN, shared experts, routers."""
    m = dims(cfg)
    return (m["layers"] * m["attn"] + m["dense_layers"] * m["dense_ffn"]
            + m["moe_layers"] * (m["shared"] + m["router"]))


def weight_bytes(cfg: dict, experts_hit: int) -> int:
    """Bytes of the weights a call reads: everything a token passes
    through, the head, and the ``experts_hit`` held experts (summed over
    the expert layers) that its tokens chose; embedding rows aside."""
    m = dims(cfg)
    return ITEM * (token_params(cfg) + m["head"] + experts_hit * m["expert"])


def latent_bytes_per_token(cfg: dict) -> int:
    """The cached latent and rope key of one position, all layers."""
    m = dims(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * ITEM


def attn_flops(cfg: dict, pairs: float, queries: int, keys: int) -> float:
    """Causal attention of ``pairs`` query-key pairs, all layers, in the
    cheaper MLA form: expanded (K and V built from each key's latent, then
    scores and values at the head widths) or absorbed (each query taken
    into the latent space, scores and values at the latent width)."""
    m = dims(cfg)
    h, r = m["h"], m["r"]
    expand = 2 * r * h * (m["nope"] + m["vh"])          # per key or per query
    expanded = 2 * h * (m["nope"] + m["rope"] + m["vh"]) * pairs + expand * keys
    absorbed = 2 * h * (2 * r + m["rope"]) * pairs + expand * queries
    return m["layers"] * min(expanded, absorbed)


def prefill_flops(cfg: dict, s: int, moe_held: int) -> float:
    """A prompt of ``s`` tokens whose expert layers routed ``moe_held``
    assignments to held experts: every layer for every token, causal
    attention, the head for the last token only."""
    m = dims(cfg)
    return (2.0 * s * token_params(cfg) + 2.0 * moe_held * m["expert"]
            + 2.0 * m["head"] + attn_flops(cfg, s * (s + 1) / 2, s, s))


def prefill_bytes(cfg: dict, s: int, experts_hit: int) -> float:
    return weight_bytes(cfg, experts_hit) + s * latent_bytes_per_token(cfg)


def decode_flops(cfg: dict, rows: int, live_tokens: int, moe_held: int) -> float:
    """One decode step of ``rows`` live rows attending ``live_tokens`` keys
    in all (each row's context, its new token in)."""
    m = dims(cfg)
    return (2.0 * rows * (token_params(cfg) + m["head"])
            + 2.0 * moe_held * m["expert"]
            + attn_flops(cfg, live_tokens, rows, live_tokens))


def decode_bytes(cfg: dict, live_tokens: int, experts_hit: int) -> float:
    """The weights once, with only the held experts hit, and the latents of
    the live positions."""
    return weight_bytes(cfg, experts_hit) + live_tokens * latent_bytes_per_token(cfg)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of operations over peak rate and bytes over bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
