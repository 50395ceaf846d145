"""Per-architecture smoke tests (assignment requirement).

For each of the ten assigned architectures: instantiate a REDUCED config of
the same family and run one forward + one train step on CPU, asserting
output shapes and the absence of NaNs.  Also checks the prefill→decode path
against the full-forward oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.tiny import tiny_config
from repro.models import build_model

ARCHS = list_archs()
B, S = 2, 32


def make_batch(cfg, key, batch=B, seq=S):
    ks = jax.random.split(key, 4)
    batch_d = {
        "tokens": jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab),
    }
    if cfg.enc_layers:
        batch_d["frames"] = jax.random.normal(ks[2], (batch, seq, cfg.d_model),
                                              cfg.activation_dtype)
    if cfg.vlm_prefix:
        batch_d["patches"] = jax.random.normal(
            ks[3], (batch, cfg.vlm_prefix, cfg.d_model), cfg.activation_dtype)
    return batch_d


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full config must carry the assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "mamba2-370m": (48, 1024, 1, 1, 0, 50280),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 16384, 202048),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
    assert got == expected, f"{arch}: {got} != {expected}"


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, key):
    cfg = tiny_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = make_batch(cfg, key)
    logits, aux = jax.jit(m.forward)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all(), arch
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch, key):
    cfg = tiny_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = make_batch(cfg, key)

    def loss_fn(p):
        return m.loss(p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), arch
    leaves = jax.tree.leaves(grads)
    assert leaves, arch
    for g in leaves:
        assert np.isfinite(np.asarray(g, dtype=np.float32)).all(), arch
    # a simple SGD step must change the loss
    params2 = jax.tree.map(lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads)
    loss2 = jax.jit(loss_fn)(params2)
    assert float(loss2) != float(loss)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_match_forward(arch, key):
    cfg = tiny_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = make_batch(cfg, key)
    cache = m.init_cache(B, 2 * S, enc_len=S if cfg.enc_layers else 0)
    logits_pre, cache = jax.jit(m.prefill)(params, batch, cache)
    nxt = batch["tokens"][:, :1]
    logits_dec, cache = jax.jit(m.decode_step)(params, cache, nxt)

    batch2 = dict(batch, tokens=jnp.concatenate([batch["tokens"], nxt], axis=1))
    full, _ = jax.jit(m.forward)(params, batch2)
    np.testing.assert_allclose(np.asarray(logits_pre[:, -1]), np.asarray(full[:, S - 1]),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(logits_dec[:, -1]), np.asarray(full[:, S]),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ARCHS)
def test_multi_token_decode_consistency(arch, key):
    """Greedy 4-step decode must equal slicing the full forward pass."""
    cfg = tiny_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = make_batch(cfg, key)
    cache = m.init_cache(B, 2 * S, enc_len=S if cfg.enc_layers else 0)
    _, cache = jax.jit(m.prefill)(params, batch, cache)
    toks = batch["tokens"]
    step = jax.jit(m.decode_step)
    for t in range(3):
        nxt = jax.random.randint(jax.random.fold_in(key, t), (B, 1), 0, cfg.vocab)
        toks = jnp.concatenate([toks, nxt], axis=1)
        logits_dec, cache = step(params, cache, nxt)
    full, _ = jax.jit(m.forward)(params, dict(batch, tokens=toks))
    np.testing.assert_allclose(np.asarray(logits_dec[:, -1]), np.asarray(full[:, -1]),
                               atol=3e-3, rtol=3e-3)


def test_long500k_eligibility():
    """Exactly the sub-quadratic archs run long_500k (documented skip list)."""
    from repro.configs import cell_is_runnable
    runnable = {a for a in ARCHS
                if cell_is_runnable(get_config(a), SHAPES["long_500k"])[0]}
    assert runnable == {"mamba2-370m", "jamba-v0.1-52b"}
