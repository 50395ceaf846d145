"""The main path's programs compiled for a described TPU v5e at real sizes.

Nothing runs: each test compiles for a chip that is described, not attached,
so the chip's compiler checks tiling, fast-memory use and the fit in HBM.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model
from repro.models.paged_cache import paginate_cache
from repro.serve.engine import executor_programs

#: HBM the v5e compiler lets one program use ("... of 15.75G hbm").
V5E_HBM_BYTES = int(15.75 * 2**30)
ARCH = "starcoder2-3b"
SLOTS, MAX_SEQ, PAGE_TOKENS = 8, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _on(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def served(one_chip):
    """Full-width starcoder2-3b with bfloat16 weights, as shapes on one chip."""
    cfg = get_config(ARCH)
    model = build_model(cfg.replace(param_dtype=cfg.dtype))
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    return model, params


def test_tiled_accumulate_kernel_at_gradient_hop(one_chip, monkeypatch):
    # the kernel a tiled-routed Window.accumulate runs, at the size of one
    # ring hop of the four-chip gradient sync: starcoder2-3b at 2 layers,
    # its flat gradient vector split 4 ways — not a block multiple
    model = build_model(get_config(ARCH).replace(n_layers=2))
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    hop = -(-n // 4)
    kacc = importlib.import_module("repro.kernels.accumulate")
    assert hop % 1024, hop
    monkeypatch.setattr(kacc, "interpret_mode", lambda: False)
    x = jax.ShapeDtypeStruct((hop,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda b, u: kacc.accumulate(b, u, op="sum")).lower(
        x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_fits_hbm(served, one_chip):
    model, params = served
    cache = _on(jax.eval_shape(lambda: paginate_cache(
        model.init_cache(SLOTS, MAX_SEQ), PAGE_TOKENS)), one_chip)
    tokens = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(params, cache, tokens).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


def test_one_slot_prefill_fits_hbm(served, one_chip):
    model, params = served
    cache = _on(jax.eval_shape(lambda: model.init_cache(1, MAX_SEQ)), one_chip)
    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.prefill).lower(
        params, {"tokens": tokens}, cache).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


#: each serving cell's model and engine size: (n_slots, max_seq), the
#: prompt length its prefill is compiled for, and the parent's decode
#: temporaries (GiB), which the in-place pools must not exceed
CELLS = {
    "starcoder2-3b": ((32, 4096), 2048, 0.126),
    "deepseek-v2-lite": ((16, 8192), 1024, 0.475),
}


def _cell_model(arch):
    cfg = get_config(arch).replace(dtype="bfloat16", param_dtype="bfloat16")
    if cfg.moe is not None:       # the chip's share: 8 of the 64 experts
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, experts_held=(0, 8)))
    return build_model(cfg)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", sorted(CELLS))
def test_executor_writes_the_pools_in_place(one_chip, arch, program):
    """The executor's decode and prefill_into_slot at each serving cell's
    size: the donated cache's page pools alias their outputs whole, and
    no pool-shaped copy is left in the compiled program.  A decode
    returns no pool bytes it does not alias (``output_size_in_bytes``
    counts the aliased outputs too, so the check is on the difference),
    and needs no more temporaries than before the pools were carried."""
    (n_slots, max_seq), prompt, parent_temp_gib = CELLS[arch]
    model = _cell_model(arch)
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    fresh, decode, prefill = executor_programs(
        model, n_slots=n_slots, max_seq=max_seq, paged_kv=True,
        page_tokens=PAGE_TOKENS)
    cache = _on(jax.eval_shape(fresh), one_chip)
    pools = {jax.tree_util.keystr(path): x
             for path, x in jax.tree_util.tree_leaves_with_path(cache)
             if jax.tree_util.keystr(path).endswith("_pages']")}
    pool_bytes = sum(x.size * x.dtype.itemsize for x in pools.values())
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    if program == "decode":
        compiled = decode.lower(params, cache,
                                spec((n_slots, 1), jnp.int32)).compile()
    else:
        pages = max_seq // PAGE_TOKENS
        compiled = prefill.lower(
            params, cache, spec((1, prompt), jnp.int32), spec((), jnp.int32),
            spec((pages,), jnp.int32), spec((pages,), jnp.bool_)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes
    if program == "decode":
        assert m.output_size_in_bytes - m.alias_size_in_bytes < 0.01 * pool_bytes
        assert m.temp_size_in_bytes <= parent_temp_gib * 2**30
    # a pool, or one layer of a pool stacked over the scanned layers
    shapes = {x.shape for x in pools.values()} | {
        x.shape[1:] for key, x in pools.items() if key.startswith("['scan']")}
    dims = "|".join(re.escape(",".join(map(str, s))) for s in shapes)
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r" copy(-start)?\(", line)
              and re.search(rf"= \(?\w+\[({dims})\]", line)]
    assert not copies, copies
