"""The plain reference against the program's own float32 forward pass and
loss, on the benchmark's seeded weights at a small size on the CPU: the
reference reads the program's weight layout right, and the benchmark's
weights repeat per seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import common, program, reference, weights


def small(name="starcoder2-3b-serve", **kw):
    cfg = common.config(name)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=300, **kw)
    return cfg


@pytest.mark.parametrize("tie", [True, False])
def test_forward_matches_the_program(tie):
    cfg = small(tie_word_embeddings=tie)
    mc = program.model_config(cfg, {"dtype": "float32", "param_dtype": "float32"})
    model = program.build_model(mc)
    w = weights.make(program.param_shapes(model), 3, cfg["initializer_range"] * 20,
                     "float32")
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, 300)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(model.forward)(w, {"tokens": tokens})
        got = jax.jit(lambda w, t: reference.logits(w, reference.hidden(w, t, cfg), cfg))(
            w, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[..., :300],
                               rtol=2e-4, atol=2e-4)
    labels = jnp.roll(tokens, -1, 1)
    with jax.default_matmul_precision("highest"):
        want = model.loss(w, {"tokens": tokens, "labels": labels})[0]
        got = reference.loss(w, tokens, labels, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_weights_repeat_per_seed_and_differ_across_seeds():
    cfg = small()
    mc = program.model_config(cfg, {"dtype": "bfloat16", "param_dtype": "bfloat16"})
    shapes = program.param_shapes(program.build_model(mc))
    big = 2**31 + 11
    a = weights.make(shapes, big, 0.02, "bfloat16")
    b = weights.make(shapes, big, 0.02, "bfloat16")
    c = weights.make(shapes, big + 2**32, 0.02, "bfloat16")
    for x, y, z in zip(*map(jax.tree.leaves, (a, b, c))):
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))


def test_served_gap_is_zero_for_the_argmax_and_positive_otherwise():
    cfg = small()
    mc = program.model_config(cfg, {"dtype": "float32", "param_dtype": "float32"})
    w = weights.make(program.param_shapes(program.build_model(mc)), 5, 0.3, "float32")
    tokens = jax.random.randint(jax.random.key(2), (1, 32), 0, 300)
    at = jnp.arange(8, 16)
    best = jnp.argmax(reference.logits(w, reference.hidden(w, tokens, cfg), cfg)[0, at], -1)
    gaps = reference.served_gaps(w, tokens, at, best, cfg)
    np.testing.assert_array_equal(np.asarray(gaps), 0.0)
    wrong = reference.served_gaps(w, tokens, at, (best + 1) % 300, cfg)
    assert float(wrong.min()) > 0
