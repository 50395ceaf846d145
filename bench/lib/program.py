"""What the benchmark takes from the program: its model configuration
class, model builder, serving engine and train step.  Nothing else in
``bench/lib`` imports the program."""
from __future__ import annotations

from bench.lib import common

# published key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "norm_epsilon": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
# published value -> what the program's ModelConfig must say
REQUIRED = {
    ("norm_type", "layer_norm"): ("norm", "layernorm"),
    ("hidden_act", "gelu_pytorch_tanh"): ("act", "gelu"),
    ("use_bias", True): ("attn_bias", True),
}


def model_config(cfg: dict, run: dict):
    """The program's ModelConfig for configuration file ``cfg``, with the
    run settings ``run`` (dtype, param_dtype, remat)."""
    common.use_program()
    from repro.configs import get_config

    mc = get_config(cfg["registry"]).replace(
        **{field: cfg[key] for key, field in FIELDS.items()},
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        dtype=run["dtype"], param_dtype=run["param_dtype"],
        remat=run.get("remat", "block"))
    for (key, value), (field, want) in REQUIRED.items():
        if cfg.get(key) == value and getattr(mc, field) != want:
            raise common.BenchError(f"{cfg['name']}: {key}={value!r} but the "
                                    f"program has {field}={getattr(mc, field)!r}")
    return mc


def build_model(mc):
    from repro.models import build_model as build

    return build(mc)


def param_shapes(model):
    import jax

    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def serve_engine(model, params, run: dict):
    from repro.serve.engine import ServeEngine

    return ServeEngine(model, params, n_slots=run["n_slots"],
                       max_seq=run["max_seq"], paged_kv=True,
                       page_tokens=run["page_tokens"], policy=run["policy"])


def pages_reserved(eng) -> tuple[int, int]:
    """KV pages the engine's pool has handed out, and the pool's size."""
    return eng.pool.n_pages - eng.pool.n_free, eng.pool.n_pages


def request(rid: int, prompt, max_new: int):
    from repro.serve.engine import Request

    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new)


def optimizer_config(opt: dict):
    from repro.train.optimizer import OptimizerConfig

    return OptimizerConfig(**opt)


def init_opt_state(params):
    from repro.train.optimizer import init_opt_state as init

    return init(params)


def shard_map(f, mesh, in_specs, out_specs):
    from repro import compat

    return compat.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def train_step(model, opt_cfg, *, grad_sync: str, n: int):
    from repro.train.trainstep import make_train_step

    return make_train_step(model, opt_cfg, grad_sync=grad_sync,
                           data_axis="data", data_axis_size=n)
