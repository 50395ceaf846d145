"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, so the reference can read the very same
values without taking anything the program made.  Only the layout (the
tree of shapes) comes from the program.  Every matrix is drawn from
N(0, init_range^2) (the published ``initializer_range``), biases from
N(0, 0.02^2), norm scales from 1 + N(0, 0.1^2).
"""
from __future__ import annotations

import numpy as np


def key_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from any whole-number seed (past 32 bits too)."""
    w = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return int(w[0]), int(w[1])


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def make(shapes, seed: int, init_range: float, dtype, sharding=None):
    """Arrays shaped like ``shapes`` (a tree of ``ShapeDtypeStruct``), in
    ``dtype``, from ``seed``."""
    import jax
    import jax.numpy as jnp

    paths, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in paths]
    specs = [(s.shape, s.dtype) for _, s in paths]

    def draw(key):
        out = []
        for i, (name, (shape, _)) in enumerate(zip(names, specs)):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            last = name.rsplit("/", 1)[-1]
            if last == "scale":
                x = 1.0 + 0.1 * z
            elif last.startswith("b"):
                x = 0.02 * z
            else:
                x = init_range * z
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(tdef, out)

    w0, w1 = key_words(seed)
    key = jax.random.fold_in(jax.random.key(w0), w1)
    return jax.jit(draw, out_shardings=sharding)(key)
