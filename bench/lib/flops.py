"""Operations and bytes a call needs, from the configuration's shapes.

Counts are of the work the algorithm needs, not of what the code does:
causal attention reads only the positions before the query, a decode
step reads the keys and values of live positions only, and recomputed
operations are not counted.  Sizes are in the published config's names.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    kv = cfg["num_key_value_heads"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * d * cfg["intermediate_size"]
    return {"d": d, "h": h, "hd": hd, "kv": kv, "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "per_layer": per_layer}


def matmul_params(cfg: dict) -> int:
    """Weights a token passes through, head included."""
    m = dims(cfg)
    return m["layers"] * m["per_layer"] + m["d"] * m["vocab"]


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of the weights one forward pass reads (embedding rows aside)."""
    return matmul_params(cfg) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    m = dims(cfg)
    return m["layers"] * 2 * m["kv"] * m["hd"] * itemsize


def attn_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` positions, all
    layers."""
    m = dims(cfg)
    return 4.0 * m["layers"] * m["h"] * m["hd"] * keys


def prefill_flops(cfg: dict, s: int) -> float:
    """A prompt of ``s`` tokens: every layer for every token, causal
    attention, the head for the last token only."""
    m = dims(cfg)
    return (2.0 * s * m["layers"] * m["per_layer"] + 2.0 * m["d"] * m["vocab"]
            + attn_flops(cfg, s * (s + 1) / 2))


def prefill_bytes(cfg: dict, s: int) -> float:
    return weight_bytes(cfg) + s * kv_bytes_per_token(cfg)


def decode_flops(cfg: dict, contexts) -> float:
    """One decode step of the live rows; ``contexts`` are their cached
    positions before the step."""
    return sum(2.0 * matmul_params(cfg) + attn_flops(cfg, c + 1) for c in contexts)


def decode_bytes(cfg: dict, contexts) -> float:
    """The weights once, the cached keys and values of the live positions,
    and the new ones written."""
    kvb = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + sum(c * kvb for c in contexts) + len(contexts) * kvb


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward (three forwards) of ``batch`` sequences of
    ``seq`` tokens, causal attention, the head at every position."""
    m = dims(cfg)
    fwd = (2.0 * batch * seq * matmul_params(cfg)
           + batch * attn_flops(cfg, seq * (seq + 1) / 2))
    return 3.0 * fwd


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of operations over peak rate and bytes over bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
