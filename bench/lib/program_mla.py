"""What the benchmark takes from the program for a DeepSeek-V2 (latent
attention, routed experts) configuration: its ``ModelConfig``, built from
the configuration file's published keys.  The model builder, the engine
and the requests are :mod:`bench.lib.program`'s.  Beside that module, the
only other file of ``bench/lib`` that imports the program."""
from __future__ import annotations

import dataclasses

from bench.lib import common

# published key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq",
}
# what the program implements of the published settings; anything else is
# refused rather than run as something it is not
SUPPORTED = {
    "hidden_act": "silu", "attention_bias": False, "scoring_func": "softmax",
    "topk_method": "greedy", "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 1, "moe_layer_freq": 1,
}


def model_config(cfg: dict, run: dict):
    """The program's ModelConfig for configuration file ``cfg``, with the
    run settings ``run`` (dtype, param_dtype, remat).  ``n_routed_experts``
    is the share this chip holds (``experts_held``); the router keeps the
    published count."""
    for key, want in SUPPORTED.items():
        if cfg[key] != want:
            raise common.BenchError(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                                    f"program implements only {want!r}")
    common.use_program()
    from repro.configs import MLAConfig, YarnConfig, get_config

    mc = get_config(cfg["registry"])
    first, stop = cfg["experts_held"]
    if stop - first != cfg["n_routed_experts"]:
        raise common.BenchError(f"{cfg['name']}: experts_held {first}-{stop} "
                                f"is not n_routed_experts={cfg['n_routed_experts']}")
    rope = {k: v for k, v in cfg["rope_scaling"].items() if k != "type"}
    if cfg["rope_scaling"]["type"] != "yarn":
        raise common.BenchError(f"{cfg['name']}: rope_scaling type "
                                f"{cfg['rope_scaling']['type']!r} is not yarn")
    moe = dataclasses.replace(
        mc.moe, num_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"],
        d_ff_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        first_dense=cfg["first_k_dense_replace"],
        d_ff_first_dense=cfg["intermediate_size"],
        renorm_gates=cfg["norm_topk_prob"], experts_held=(first, stop))
    mla = MLAConfig(q_lora=cfg["q_lora_rank"] or 0, kv_lora=cfg["kv_lora_rank"],
                    qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
                    v_head=cfg["v_head_dim"])
    return mc.replace(
        **{field: cfg[key] for key, field in FIELDS.items()},
        head_dim=cfg["v_head_dim"], act="swiglu", norm="rmsnorm",
        attn_bias=False, rope_scaling=YarnConfig(**rope), mla=mla, moe=moe,
        dtype=run["dtype"], param_dtype=run["param_dtype"],
        remat=run.get("remat", "block"))
