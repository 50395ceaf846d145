#!/usr/bin/env python3
"""Drive the main paths once on a TPU v5e and check what comes out.

  python3 chip_smoke.py               # one chip: serve starcoder2-3b
  python3 chip_smoke.py --four-chips  # four chips: the RMA gradient sync

One chip: ``starcoder2-3b`` at its published widths and all 30 layers, with
random bfloat16 weights from a seed, serves 8 requests through
``ServeEngine(paged_kv=True)`` (8 slots x 2048 positions, 32 new tokens
each).  One completed request of each prompt length is then re-run
through the cache-free ``model.forward`` in float32, and every served token
must sit within ``TOL_SIGMA`` of its row's best logit.

Four chips: one data-parallel train step of ``starcoder2-3b`` at its
published widths, depth cut to ``TRAIN_LAYERS``, over a 4-device ``data``
mesh, with the gradients synced by the one-sided RMA ring
(``make_train_step(grad_sync="rma_ring")`` under ``shard_map``).  The
first step's synced gradients are checked against a ``psum`` of the same
local gradients, and a few seeded steps against the same step under
``grad_sync="gspmd"``.  With this option no other phase runs.

The last line of standard output is one JSON object naming the device.
When JAX finds no TPU, or any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compat  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.pipeline import DataConfig, make_source  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.train.optimizer import OptimizerConfig, init_opt_state  # noqa: E402
from repro.train.trainstep import make_grad_sync, make_train_step  # noqa: E402

ARCH = "starcoder2-3b"
SEED = 0

N_SLOTS, MAX_SEQ, PAGE_TOKENS = 8, 2048, 16
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LENS = (256, 1024)   # two lengths: prefill compiles at most twice
#: How far below its row's best reference logit a served token may sit, in
#: standard deviations of that row.  The server computes in bfloat16 (8
#: significant bits) through 30 layers and a bf16 KV cache, so near-ties in
#: the float32 reference may flip; the top two logits of a 49152-way row
#: are typically a few hundredths of a deviation apart.  A wrong token — a
#: bad cache page, a wrong position — lands near the row mean, about four
#: deviations below the best, and fails by a wide margin.
TOL_SIGMA = 0.25

#: Depth of the four-chip train step.  Weights, Adam state, gradients and
#: the flattened gradient vector are all replicated on every chip, so the
#: published 30 layers cannot fit one v5e's 16 GB.  Compiled for a v5e:2x2,
#: the rma_ring step needs 10.95 GiB per chip at 1 layer, 11.61 at 2.
TRAIN_LAYERS = 2
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 256, 3
#: Same tolerance as the flat ring-vs-reference acceptance in
#: tests/mdev/rma_topology.py: reassociated ring adds against the
#: partitioner's reduction, amplified by Adam's 1/sqrt(v).
TRAIN_ATOL, TRAIN_RTOL = 3e-3, 1e-2


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def require_tpu(count: int) -> list:
    """The first JAX work: the devices must be TPUs, at least ``count``."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devices)} "
             f"{devices[0].platform} device(s)")
    if len(devices) < count:
        fail(f"needs {count} TPU chips, JAX sees {len(devices)}")
    return devices


# -- one chip: serving -------------------------------------------------------

def serve_phase(cfg) -> None:
    """Serve ``N_REQUESTS`` through the paged engine and check the first
    completed request of each prompt length against the float32 cache-free
    forward."""
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(SEED)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[serve] model {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
          f"{n_params / 1e9:.3f} B params, weights {cfg.param_dtype}",
          flush=True)

    eng = ServeEngine(model, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                      paged_kv=True, page_tokens=PAGE_TOKENS)
    rng = np.random.default_rng(SEED)
    lens = rng.permutation(np.repeat(PROMPT_LENS, N_REQUESTS // len(PROMPT_LENS)))
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32) for n in lens]

    # warm-up: one short request per prompt length compiles both prefills
    # and the decode step, so the timed window compiles nothing
    for i, n in enumerate(PROMPT_LENS):
        eng.submit(Request(rid=-1 - i, prompt=prompts[list(lens).index(n)],
                           max_new_tokens=2))
    eng.run(strict=True)
    setup_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    for rid, prompt in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=MAX_NEW))
    done = [c for c in eng.run(strict=True) if c.rid >= 0]
    serve_s = time.perf_counter() - t1
    n_tokens = sum(len(c.tokens) for c in done)
    print(f"[serve] {len(done)}/{N_REQUESTS} requests completed, "
          f"{n_tokens} tokens served ({MAX_NEW} per request)", flush=True)
    print(f"[serve] set-up (init + compile + warm-up) {setup_s:.3f} s, "
          f"serve after warm-up {serve_s:.3f} s", flush=True)
    check(len(done) == N_REQUESTS and all(c.finished for c in done),
          f"{len(done)} of {N_REQUESTS} requests finished")
    check(all(len(c.tokens) == MAX_NEW for c in done),
          f"token counts {[len(c.tokens) for c in done]}, want {MAX_NEW}")
    stats = jax.devices()[0].memory_stats() or {}
    check("peak_bytes_in_use" in stats, "device reports no peak_bytes_in_use")
    print(f"[serve] peak_bytes_in_use {stats['peak_bytes_in_use']}", flush=True)
    del eng

    # the served tokens against the float32 cache-free forward: position
    # len(prompt)-1+i of prompt+generated[:-1] predicts generated token i
    ref = jax.jit(build_model(cfg.replace(dtype="float32")).forward)
    for n in PROMPT_LENS:
        comp = min((c for c in done if len(prompts[c.rid]) == n),
                   key=lambda c: c.rid)
        seq = np.concatenate([prompts[comp.rid],
                              np.asarray(comp.tokens[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            logits, _ = ref(params, {"tokens": jnp.asarray(seq)[None]})
        rows = np.asarray(logits[0, n - 1:, :cfg.vocab], np.float32)
        served = np.asarray(comp.tokens)
        margin = (rows.max(-1) - rows[np.arange(len(served)), served]) / rows.std(-1)
        n_top = int((rows.argmax(-1) == served).sum())
        worst = float(margin.max())
        print(f"[serve] logits check (rid {comp.rid}, prompt {n}): "
              f"{n_top}/{len(served)} served tokens are the float32 argmax, "
              f"worst gap {worst:.4f} sigma (tolerance {TOL_SIGMA} sigma): "
              f"{'passed' if worst <= TOL_SIGMA else 'FAILED'}", flush=True)
        check(worst <= TOL_SIGMA, f"rid {comp.rid}: served token {worst:.4f} "
              f"sigma below the float32 row max (tolerance {TOL_SIGMA})")


# -- four chips: the RMA gradient sync ---------------------------------------

def train_steps(model, opt_cfg, mesh):
    """The ``rma_ring`` and ``gspmd`` train steps over ``mesh``'s ``data``
    axis, jitted with parameters and optimizer state donated.

    The ring step returns the loss averaged over the data axis (each device
    computes its own shard's) and, per device, the sum of the tokens it
    held, so the caller can see which batch shard each device got."""
    n = mesh.shape["data"]
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    ring = make_train_step(model, opt_cfg, grad_sync="rma_ring",
                           data_axis="data", data_axis_size=n)

    def ring_body(params, opt, batch):
        params, opt, metrics = ring(params, opt, batch)
        held = batch["tokens"].sum(dtype=jnp.int32)[None]
        return params, opt, lax.pmean(metrics["loss"], "data"), held

    ring_step = jax.jit(
        compat.shard_map(ring_body, mesh=mesh,
                         in_specs=(P(), P(), P("data")),
                         out_specs=(P(), P(), P(), P("data"))),
        donate_argnums=(0, 1))
    gspmd = make_train_step(model, opt_cfg, grad_sync="gspmd")

    def gspmd_body(params, opt, batch):
        params, opt, metrics = gspmd(params, opt, batch)
        return params, opt, metrics["loss"]

    gspmd_step = jax.jit(gspmd_body, in_shardings=(rep, rep, rows),
                         out_shardings=(rep, rep, rep), donate_argnums=(0, 1))
    return ring_step, gspmd_step


def grad_check_step(model, mesh):
    """``(params, batch) -> (ring, control)``: per device, the worst ratio
    of the gap between the gradients after the ``rma_ring`` sync and a
    ``psum`` of the same local gradients to its bound; and the same ratio
    for a control that skips the exchange.

    Two float32 sums of the same n numbers in different orders differ by at
    most 2(n-1)(u*sum|g| + tiny) per element (u = 2**-24 per add, ``tiny``
    for a result flushed to zero); both are then divided by n, so the bound
    is 2(n-1)(u*mean|g| + tiny).  A correct ring reads at most 1; a missing
    or doubled shard reads orders of magnitude above it."""
    n = mesh.shape["data"]
    sync = make_grad_sync(grad_sync="rma_ring", data_axis="data",
                          data_axis_size=n)
    f32 = jnp.finfo(jnp.float32)

    def body(params, batch):
        grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
        # one materialized copy feeds both the ring and the psum
        grads = lax.optimization_barrier(
            jax.tree.map(lambda g: g.astype(jnp.float32), grads))
        synced = sync(grads)
        ring = local = jnp.zeros((), jnp.float32)
        for g, s in zip(jax.tree.leaves(grads), jax.tree.leaves(synced)):
            ref = lax.pmean(g, "data")
            mean_abs = lax.pmean(jnp.abs(g), "data")
            bound = 2 * (n - 1) * (float(f32.eps) / 2 * mean_abs
                                   + float(f32.tiny))
            ring = jnp.maximum(ring, jnp.max(jnp.abs(s - ref) / bound))
            local = jnp.maximum(local, jnp.max(jnp.abs(g - ref) / bound))
        return ring[None], local[None]

    return jax.jit(compat.shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                                    out_specs=(P("data"), P("data"))))


def _run_steps(step, model, mesh, batches):
    """Fresh seeded state, compile, ``len(batches)`` steps; returns the
    compiled text, the losses, the per-device token sums of the last step
    and the final parameters on the host."""
    rep = NamedSharding(mesh, P())
    params = jax.jit(model.init, out_shardings=rep)(jax.random.PRNGKey(SEED))
    opt = jax.jit(init_opt_state, out_shardings=rep)(params)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    losses, held = [], None
    t0 = time.perf_counter()
    for batch in batches:
        params, opt, loss, *rest = compiled(params, opt, batch)
        losses.append(float(loss))
        held = rest[0] if rest else None
    steps_s = time.perf_counter() - t0
    host = jax.device_get(params)
    return compiled.as_text(), losses, held, host, compile_s, steps_s


def train_phase(cfg, *, mesh) -> None:
    """The ``rma_ring`` gradient sync against a ``psum``, then ``rma_ring``
    against ``gspmd`` for ``TRAIN_STEPS`` seeded steps."""
    n = mesh.shape["data"]
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=0,
                              total_steps=TRAIN_STEPS)
    rows = NamedSharding(mesh, P("data"))
    data = make_source(DataConfig(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                                  global_batch=TRAIN_BATCH, seed=SEED))
    host_batches = [data.batch_at(s) for s in range(TRAIN_STEPS)]
    batches = [jax.device_put(b, rows) for b in host_batches]

    params = jax.jit(model.init, out_shardings=NamedSharding(mesh, P()))(
        jax.random.PRNGKey(SEED))
    ring_r, local_r = map(np.asarray,
                          grad_check_step(model, mesh)(params, batches[0]))
    del params
    print(f"[train] step-1 gradients, rma_ring vs psum: worst gap "
          f"{ring_r.max():.4f} of the float32 reassociation bound per device "
          f"{ring_r.tolist()}; control without the exchange: "
          f"{local_r.min():.4g} or more", flush=True)
    check(bool((ring_r <= 1).all()), "rma_ring gradients differ from the psum "
          f"beyond the reassociation bound ({ring_r.tolist()})")
    check(bool((local_r > 1).all()), "the gradient check cannot tell unsynced "
          f"gradients from synced ones ({local_r.tolist()})")

    ring_step, gspmd_step = train_steps(model, opt_cfg, mesh)
    txt, ring_losses, held, ring_params, c_s, s_s = _run_steps(
        ring_step, model, mesh, batches)
    print(f"[train] rma_ring: compile {c_s:.3f} s, {TRAIN_STEPS} steps "
          f"{s_s:.3f} s, losses {ring_losses}", flush=True)
    _, gspmd_losses, _, gspmd_params, c_s, s_s = _run_steps(
        gspmd_step, model, mesh, batches)
    print(f"[train] gspmd:    compile {c_s:.3f} s, {TRAIN_STEPS} steps "
          f"{s_s:.3f} s, losses {gspmd_losses}", flush=True)

    want = host_batches[-1]["tokens"].reshape(n, -1).sum(1)
    got = np.asarray(held)
    print(f"[train] per-device token sums {got.tolist()}, "
          f"batch shards {want.tolist()}", flush=True)
    check(np.array_equal(got, want), "a device did not hold its own batch shard")
    loss_diff = float(np.max(np.abs(np.subtract(ring_losses, gspmd_losses))))
    check(np.allclose(ring_losses, gspmd_losses, atol=TRAIN_ATOL,
                      rtol=TRAIN_RTOL), f"losses differ by {loss_diff}")
    worst, max_diff = -np.inf, 0.0
    for a, b in zip(jax.tree.leaves(ring_params), jax.tree.leaves(gspmd_params)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        d = np.abs(a - b)
        max_diff = max(max_diff, float(d.max()))
        worst = max(worst, float((d - (TRAIN_ATOL + TRAIN_RTOL * np.abs(b))).max()))
    print(f"[train] rma_ring vs gspmd: max loss diff {loss_diff}, max param "
          f"diff {max_diff} (atol {TRAIN_ATOL}, rtol {TRAIN_RTOL}): "
          f"{'agree' if worst <= 0 else 'DISAGREE'}", flush=True)
    check(worst <= 0, f"parameters differ beyond tolerance (max diff {max_diff})")
    n_cp = txt.count("collective-permute")
    print(f"[train] rma_ring step: {n_cp} collective-permute", flush=True)
    check(n_cp > 0, "no collective-permute in the rma_ring step")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel train step")
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)
    print(f"[chip] {len(devices)} x {devices[0].device_kind}, compile cache "
          f"{use_compile_cache()}", flush=True)
    published = get_config(ARCH)
    if args.four_chips:
        check(len(devices) == 4, f"--four-chips needs a 4-chip host, JAX sees "
              f"{len(devices)}")
        print(f"[train] {ARCH} at published widths, depth cut "
              f"{published.n_layers} -> {TRAIN_LAYERS} layers (weights, Adam "
              f"state, gradients and the gradient vector are replicated per "
              f"chip)", flush=True)
        train_phase(published.replace(n_layers=TRAIN_LAYERS),
                    mesh=compat.make_mesh((4,), ("data",)))
    else:
        serve_phase(published.replace(param_dtype=published.dtype))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
