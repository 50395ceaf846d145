"""Train-step builders: loss → grads → (optional RMA grad sync) → AdamW.

Two gradient-synchronization modes:

* ``"gspmd"`` (default): the step is jit-compiled with sharded params/batch;
  XLA's partitioner inserts the reduce-scatter/all-gather/all-reduce
  collectives implied by the shardings.  This is the baseline the roofline
  analysis measures.
* ``"rma_ring"``: data-parallel gradient sync through the paper's window
  layer (one-sided ring all-reduce inside ``shard_map``), with P2 ordering —
  see ``repro.core.rma.collectives``.  The ring runs on a **sum-specialized
  dup** of the gradient window (``same_op="sum"``, paper §2.3 hints × P4),
  so every reduce hop lowers through the accumulate engine's specialized
  path.  Used by benchmarks/examples and the cross-pod put+signal exchange;
  optionally with error-feedback gradient compression
  (``repro.train.compress``).

``moe_ep`` selects the MoE expert-parallel dispatch for the step's model:
``"gspmd"`` (partitioner-inserted all-to-all) or ``"rma"`` (the one-sided
token exchange of ``repro.core.rma.alltoall`` inside ``shard_map`` over the
expert axis — see ``docs/moe_ep.md``).  It is carried on the model config
(``MoEConfig.ep_mode``), so the same switch serves jit and shard_map paths.

Gradient accumulation scans over microbatches.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.train.optimizer import (
    OptimizerConfig,
    adamw_update,
    init_opt_state,
)

Array = jax.Array


def make_grad_sync(*, grad_sync: str = "gspmd", data_axis: str | None = None,
                   data_axis_size: int = 1, compressor=None, topology=None,
                   backend: str = "rma"):
    """The gradient sync :func:`make_train_step` applies between the
    gradients and AdamW: ``sync_grads(grads) -> grads``.

    Under ``"rma_ring"`` (inside ``shard_map`` over ``data_axis``) it
    returns the gradients averaged over the axis; otherwise it returns them
    unchanged (the partitioner inserts the collectives, or the caller syncs
    compressed gradients itself)."""

    def sync_grads(grads):
        if grad_sync == "gspmd" or data_axis is None or data_axis_size == 1:
            return grads  # partitioner-inserted collectives
        if compressor is not None:
            return grads  # handled at caller level with state
        from repro.core.rma.collectives import plan_all_reduce
        from repro.core.rma.topology import default_topology
        from repro.core.rma.window import Window, WindowConfig

        topo = (topology if topology is not None
                else default_topology(data_axis_size))

        # One window, one ring, all leaves: the whole gradient pytree is
        # synced as a single concatenated vector, so the per-step cost is
        # one 2(n-1)-phase ring plus one exit flush epoch — not a ring (and
        # a flush) per leaf.  Gradient sync is a pure same-op (sum)
        # accumulate stream, so declare it: the ring runs on a
        # sum-specialized dup of the gradient window (paper §2.3 hints × P4
        # dup), lowering every reduce hop through the accumulate engine's
        # specialized path.  The exchange is a declarative-plan replay
        # (``collectives.all_reduce_plan``): the schedule is planned once
        # per gradient-vector shape and every subsequent step is pure
        # issue — build-once, execute-many.
        flat, tdef = jax.tree.flatten(grads)
        sizes = [g.size for g in flat]
        vec = jnp.concatenate([g.reshape(-1).astype(jnp.float32) for g in flat])
        win = Window.allocate(
            vec, data_axis, data_axis_size,
            WindowConfig(scope="thread", order=True, accumulate_ops=("sum",),
                         topology=topo))
        sumwin = win.dup_with_info(same_op="sum")
        vec = plan_all_reduce(vec, data_axis, data_axis_size, order=True,
                              win=sumwin, topology=topo,
                              backend=backend) / data_axis_size
        out, off = [], 0
        for g, n in zip(flat, sizes):
            out.append(vec[off:off + n].reshape(g.shape))  # f32, as before
            off += n
        return jax.tree.unflatten(tdef, out)

    return sync_grads


def make_train_step(
    model,
    opt_cfg: OptimizerConfig,
    *,
    accum_steps: int = 1,
    grad_sync: str = "gspmd",
    data_axis: str | None = None,
    data_axis_size: int = 1,
    compressor=None,
    moe_ep: str | None = None,
    topology=None,
    backend: str = "rma",
):
    """Build ``train_step(params, opt_state, batch) -> (params, opt, metrics)``.

    With ``accum_steps > 1`` the batch's leading dim must be divisible by it;
    microbatches are scanned and gradients averaged.

    ``moe_ep``: override the MoE expert-parallel dispatch mode
    (``"gspmd"`` | ``"rma"``) for this step's model; requires an MoE config.

    ``topology``: the data axis's ``g hosts × l local`` factorization (a
    ``repro.core.rma.Topology``, e.g. from ``launch.mesh.mesh_topology``);
    ``None`` consults the ``RMA_TOPOLOGY`` environment override.  With a
    non-degenerate factorization the ``"rma_ring"`` gradient sync replays
    the hierarchical plan — intra-node reduce-scatter, inter-node ring over
    host leaders, intra-node all-gather — cutting inter-node phases from
    2(n−1) to 2(g−1) with bit-identical numerics.

    ``backend``: the lowering target for the ``"rma_ring"`` gradient-sync
    plan (``"auto" | "rma" | "gspmd"``); ``"auto"`` consults the
    calibrated backend latency table.  ``"interpret"`` is host-side only
    and invalid inside a training mesh.
    """
    if backend not in ("auto", "rma", "gspmd"):
        raise ValueError(
            f"backend={backend!r} invalid for a train step; expected "
            "'auto', 'rma', or 'gspmd' (the interpret target runs host-side "
            "with no mesh)")
    if moe_ep is not None:
        if model.cfg.moe is None:
            raise ValueError(
                f"moe_ep={moe_ep!r} requested but arch {model.cfg.name!r} "
                "has no MoE config")
        from repro.models import build_model

        model = build_model(model.cfg.replace(
            moe=dataclasses.replace(model.cfg.moe, ep_mode=moe_ep)))

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def grads_of(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            return loss, metrics, grads
        micro = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
            batch)

        def body(carry, mb):
            acc, loss_acc = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, loss_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, loss_sum), _ = lax.scan(body, (zeros, jnp.zeros(())), micro)
        grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        return loss_sum / accum_steps, {"xent": loss_sum / accum_steps,
                                        "aux": jnp.zeros(())}, grads

    sync_grads = make_grad_sync(grad_sync=grad_sync, data_axis=data_axis,
                                data_axis_size=data_axis_size,
                                compressor=compressor, topology=topology,
                                backend=backend)

    def train_step(params, opt_state, batch):
        loss, metrics, grads = grads_of(params, batch)
        grads = sync_grads(grads)
        params, opt_state, opt_metrics = adamw_update(grads, opt_state, params, opt_cfg)
        out = {"loss": loss, **{k: v for k, v in metrics.items()}, **opt_metrics}
        return params, opt_state, out

    return train_step


def init_train_state(model, key, opt_cfg: OptimizerConfig | None = None):
    params = model.init(key)
    return params, init_opt_state(params)


__all__ = ["make_train_step", "make_grad_sync", "init_train_state"]
