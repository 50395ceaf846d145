"""Data-parallel training: the program's train step under ``shard_map``
over a ``data`` axis of the cell's chips, gradients synced by the
one-sided RMA ring.

Set-up builds one compiled step with its state, drives it from the seed
through its first ``check_steps`` steps (distinct batches, through the
same call and feed as the window) and hands that state to the window.
After the window the float32 reference follows those first steps from
the same weights and batches, and three numbers are compared: each
step's loss, the norm of the first gradient as the optimizer got it
(read back from its first moment), and the norm of each parameter's
change over the first steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import common, program, reference, weights

clock = time.perf_counter
STEP_PROGRAM = "bench_train_step"


def build_step(model, opt_cfg, mesh, n: int):
    """``bench_train_step(params, opt, pool, k)``: one step on batch ``k``
    of the held pool, parameters and optimizer state donated."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    ring = program.train_step(model, opt_cfg, grad_sync="rma_ring", n=n)

    def body(params, opt, pool, k):
        b = lax.dynamic_index_in_dim(pool, k, 0, keepdims=False)
        params, opt, metrics = ring(params, opt, {"tokens": b[:, :-1],
                                                  "labels": b[:, 1:]})
        return params, opt, lax.pmean(metrics["loss"], "data")

    smapped = program.shard_map(body, mesh, (P(), P(), P(None, "data"), P()),
                                (P(), P(), P()))

    def bench_train_step(params, opt, pool, k):
        return smapped(params, opt, pool, k)

    return jax.jit(bench_train_step, donate_argnums=(0, 1))


def seeded_state(ctx, model, params_sharding, pool_sharding):
    """The float32 weights and the held token batches, ``(batches_held,
    global_batch, seq_len + 1)``, made on the device from the seed (the
    same values whatever the shardings)."""
    import jax
    import jax.numpy as jnp

    work = ctx.work
    params = weights.make(program.param_shapes(model), ctx.seed,
                          ctx.cfg["initializer_range"], "float32", params_sharding)
    shape = (work["batches_held"], work["global_batch"], work["seq_len"] + 1)
    w0, w1 = weights.key_words(ctx.seed + 1)
    pool = jax.jit(lambda key: jax.random.randint(key, shape, 0, model.cfg.vocab, jnp.int32),
                   out_shardings=pool_sharding)(jax.random.fold_in(jax.random.key(w0), w1))
    return params, pool


def leaf_norms(tree) -> np.ndarray:
    """The norm of every leaf (on the device where the leaves are)."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                               for x in jax.tree.leaves(t)])(tree)
    return np.asarray(jax.device_get(norms), np.float64)


def change_norms(params, p0) -> np.ndarray:
    """Per leaf, the norm of the change from the host copy ``p0``."""
    import jax

    return np.asarray([np.linalg.norm(np.asarray(a, np.float32) - b)
                       for a, b in zip(jax.tree.leaves(jax.device_get(params)),
                                       jax.tree.leaves(p0))], np.float64)


def follow(ctx, p0, batches, lowp: bool = False, device: int = 0):
    """The reference's first steps from ``p0`` on ``batches``, on one chip:
    losses, the first clipped gradient's leaf norms and the change's leaf
    norms."""
    import jax
    from jax.sharding import SingleDeviceSharding

    dev0 = SingleDeviceSharding(ctx.devices[device])
    losses, g, p = reference.train(
        jax.device_put(p0, dev0),
        [(jax.device_put(x[:, :-1], dev0), jax.device_put(x[:, 1:], dev0))
         for x in batches],
        ctx.cfg, ctx.cfg["training"]["optimizer"], rows=1, lowp=lowp)
    return losses, leaf_norms(g), change_norms(p, p0)


def compare(prog, ref, limits: dict) -> dict:
    """The numbers compared, each with its limit.  Leaves the loss does not
    move (a gradient under 1e-3 of the median leaf's in the reference, as
    a key bias under softmax has) move under AdamW by round-off alone and
    are left out of the change."""
    (lp, gp, dp), (lr, gr, dr) = prog, ref
    keep = gr >= 1e-3 * np.median(gr)
    values = {
        "loss_rel_gap": float(np.max(np.abs(np.subtract(lp, lr)) / np.abs(lr))),
        "grad_norm_gap": gap(gp, gr),
        "update_norm_gap": gap(dp, dr, keep),
    }
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf: the gap between the program's norm and the reference's,
    over the larger of the reference's norm of that leaf and of the median
    leaf."""
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, np.median(ref))))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    tcfg, work = ctx.cfg["training"], ctx.work
    mc = program.model_config(ctx.cfg, tcfg)
    model = program.build_model(mc)
    n = len(ctx.devices)
    mesh = Mesh(np.asarray(ctx.devices), ("data",))
    rep = NamedSharding(mesh, P())
    opt_cfg = program.optimizer_config(tcfg["optimizer"])

    params, pool = seeded_state(ctx, model, rep, NamedSharding(mesh, P(None, "data")))
    opt = jax.jit(program.init_opt_state, out_shardings=rep)(params)
    k_held, b, s = work["batches_held"], work["global_batch"], work["seq_len"]
    step = build_step(model, opt_cfg, mesh, n)

    # the first steps, through the window's own call and feed
    p0 = jax.device_get(params)
    losses = []
    for k in range(work["check_steps"]):
        params, opt, loss = step(params, opt, pool, jnp.int32(k))
        losses.append(float(loss))
        if k == 0:
            g1 = leaf_norms(opt["m"]) / (1 - tcfg["optimizer"]["b1"])
    d_prog = change_norms(params, p0)
    setup_s = clock() - ctx.t_start
    compiles0 = ctx.counter.count

    t_w = clock()
    end = t_w + ctx.seconds
    prof = common.TraceSlice(ctx, t_w)
    steps, k, prev = 0, work["check_steps"], None
    while True:
        now = clock()
        prof.tick(now)
        if now >= end:
            break
        with common.span("bench.train_step"):
            params, opt, loss = step(params, opt, pool, jnp.int32(k % k_held))
            if prev is not None:
                prev.block_until_ready()
        prev, steps, k = loss, steps + 1, k + 1
    prev.block_until_ready()
    t_end = clock()
    prof.close()
    in_window = ctx.counter.count - compiles0
    peak = common.peak_memory(ctx.devices)
    common.note(f"[window] {steps} steps in {t_end - t_w:.6f} s, programs "
                f"compiled in the window {in_window}, first losses {losses}")
    batches = np.asarray(jax.device_get(pool[:work["check_steps"]]))
    del params, opt, pool, prev, loss
    gc.collect()

    t0 = clock()
    ref = follow(ctx, p0, batches)
    common.note(f"[check] reference losses {ref[0]} in {clock() - t0:.3f} s")
    checks = compare((losses, g1, d_prog), ref, work["limits"])
    return {
        "correct": common.checks_pass(checks),
        "attempted": steps, "failed": 0,
        "e2e": {"setup_s": setup_s, "train_step_s": (t_end - t_w) / steps},
        "checks": checks, "memory_peak_bytes": peak,
        "record": {"cfg": ctx.cfg, "traced": (prof.t_on, prof.t_off),
                   "step_program": STEP_PROGRAM, "global_batch": b, "seq_len": s},
    }
