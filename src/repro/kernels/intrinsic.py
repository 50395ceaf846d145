"""NIC-atomic accumulate — the P3 "latency path" as a real TPU kernel.

The small-count, declared-single-op side of the accumulate crossover
(router: ``repro.core.rma.accumulate``).  The origin issues one ICI remote
DMA carrying the update into the target's staging slot; the target folds the
staged update into its window buffer with a single VPU op on arrival.  No
round-trip, no target *TensorCore* pre-arrangement beyond the declared op —
the hardware shape of ``MPI_Accumulate`` inside the atomic envelope
(paper §2.3 fn. 1: "intrinsic to the origin").

This kernel is deliberately restricted the way NIC atomics are:

* small element counts only (the caller routes large counts to the tiled
  bandwidth kernel in ``repro.kernels.accumulate``);
* one declared op per launch — the ``same_op`` contract; pass a
  ``WindowConfig`` via ``config=`` to have the declaration checked against
  the router, so a config that would *not* route here cannot be lowered
  here by accident.

Validated cross-device in the Mosaic interpreter (tests/mdev/kernels_mdev.py)
against ``repro.kernels.ref.ring_accumulate_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (ATOMIC_KERNEL_OPS, combine_op,
                                  interpret_mode)


def _acc_kernel(x_ref, buf_ref, o_ref, stage_ref, cur_vmem, in_vmem,
                send_sem, recv_sem, *, axis: str, shift: int,
                axis_size: int, offset: int, op: str):
    my = jax.lax.axis_index(axis)
    target = jax.lax.rem(my + shift + axis_size, axis_size)
    # carry the window buffer through to the output before the atomic lands
    pltpu.sync_copy(buf_ref, o_ref)
    # one remote DMA: my update into the target's staging slot
    rdma = pltpu.make_async_remote_copy(
        x_ref, stage_ref, send_sem, recv_sem,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    rdma.start()
    rdma.wait()  # send retired + my own incoming update staged
    # target side of the atomic: fold the staged update into the buffer
    # (HBM/ANY refs are DMA-only: stage through VMEM for the VPU op)
    n = x_ref.shape[0]
    pltpu.sync_copy(o_ref.at[pl.ds(offset, n)], cur_vmem)
    pltpu.sync_copy(stage_ref, in_vmem)
    cur_vmem[...] = combine_op(cur_vmem[...], in_vmem[...].astype(cur_vmem.dtype), op)
    pltpu.sync_copy(cur_vmem, o_ref.at[pl.ds(offset, n)])


def ring_accumulate(update, buffer, *, axis: str, axis_size: int,
                    shift: int = 1, op: str = "sum", offset: int = 0,
                    config=None):
    """Every device atomically accumulates ``update`` into its ring
    neighbour's ``buffer`` at ``offset``; returns the updated buffer (what
    this device's window holds after its neighbour's atomic landed).

    Call inside ``shard_map``.  ``config``: optionally derive/validate the
    path from a :class:`repro.core.rma.WindowConfig` — the same declaration
    that routes in the emulation layer must route ``intrinsic`` here, so one
    info object drives both layers."""
    if op not in ATOMIC_KERNEL_OPS:
        raise ValueError(f"op {op!r} not in {ATOMIC_KERNEL_OPS} (NIC "
                         "atomics; route other ops to repro.kernels.accumulate)")
    if op in ("band", "bor", "bxor") and not jnp.issubdtype(
            jnp.dtype(buffer.dtype), jnp.integer):
        raise ValueError(f"bitwise op {op!r} needs an integer buffer, "
                         f"got {buffer.dtype}")
    if config is not None:
        from repro.core.rma import accumulate as _engine

        path = _engine.route(op, int(update.size), update.dtype, config)
        if path != _engine.PATH_INTRINSIC:
            raise ValueError(
                f"declared usage routes this accumulate to the {path!r} "
                "path; the NIC-atomic kernel only lowers intrinsic-routed "
                "configurations (declared single-op, count <= crossover)")
    if update.shape[0] + offset > buffer.shape[0]:
        raise ValueError(
            f"accumulate of {update.shape[0]} elems at offset {offset} "
            f"overruns the {buffer.shape[0]}-elem window buffer")
    out, _ = pl.pallas_call(
        functools.partial(_acc_kernel, axis=axis, shift=shift,
                          axis_size=axis_size, offset=offset, op=op),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                   pl.BlockSpec(memory_space=pltpu.ANY)],
        # the staging slot is an output rather than scratch: remote DMA
        # needs it in ANY/HBM space
        out_shape=[jax.ShapeDtypeStruct(buffer.shape, buffer.dtype),
                   jax.ShapeDtypeStruct(update.shape, update.dtype)],
        scratch_shapes=[pltpu.VMEM(update.shape, buffer.dtype),
                        pltpu.VMEM(update.shape, update.dtype),
                        pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=interpret_mode(),
    )(update, buffer)
    return out


__all__ = ["ring_accumulate"]
