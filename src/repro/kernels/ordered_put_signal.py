"""Fused ordered put+signal — paper Listing 2 (P2) at the kernel level.

The payload DMA and the completion-flag DMA are issued back-to-back on the
same channel; the flag transfer *starts only after the payload transfer's
send side completes* (``payload.wait_send()``), so the flag can never
overtake the data — NIC-fence semantics without a full round-trip flush.
A consumer polling the flag word therefore observes data-then-flag order,
which is exactly what ``mpi_win_order=true`` buys the paper's Listing 2.

Without P2 (``ordered=False``) the kernel degrades to the Listing-1 shape:
payload, full completion wait (both semaphores — the "flush"), then flag.
The cost difference is one blocking completion on the critical path.

``accumulate_signal`` is the same fusion applied to the accumulate engine's
producer pattern: the update DMA lands in a staging slot, the target folds
it into its window buffer with the declared op, and the completion flag
chains behind on the same channel — an update and its flag in one lowered
op (the kernel twin of ``repro.core.rma.accumulate.accumulate_signal``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (ATOMIC_KERNEL_OPS, combine_op,
                                  interpret_mode)


def _put_signal_kernel(x_ref, flag_ref, o_ref, oflag_ref,
                       dsend, drecv, fsend, frecv, *,
                       axis: str, shift: int, axis_size: int, ordered: bool):
    my = jax.lax.axis_index(axis)
    target = jax.lax.rem(my + shift + axis_size, axis_size)
    data = pltpu.make_async_remote_copy(
        x_ref, o_ref, dsend, drecv,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    data.start()
    if ordered:
        # P2: fence — flag issues once the payload's send is on the wire
        # ordered behind it; no remote-completion round trip.
        data.wait_send()
    else:
        # Listing 1: full flush (remote completion) before the signal.
        data.wait()
    flag = pltpu.make_async_remote_copy(
        flag_ref, oflag_ref, fsend, frecv,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    flag.start()
    flag.wait()
    if ordered:
        data.wait_recv()  # drain before kernel exit


def put_signal(x, flag, *, axis: str, axis_size: int, shift: int = 1,
               ordered: bool = True, config=None):
    """Ring put of ``x`` plus a flag word; returns (received, received_flag).

    Call inside ``shard_map``.  ``ordered=True`` is the paper's P2 path.

    ``config``: optionally derive the path from a
    :class:`repro.core.rma.WindowConfig` — the same info object that selects
    the path in the ``Window`` emulation layer — so one declaration drives
    both the HLO model and this kernel twin."""
    if config is not None:
        ordered = config.order
    return pl.pallas_call(
        functools.partial(_put_signal_kernel, axis=axis, shift=shift,
                          axis_size=axis_size, ordered=ordered),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                   pl.BlockSpec(memory_space=pltpu.ANY)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(flag.shape, flag.dtype)],
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 4,
        interpret=interpret_mode(),
    )(x, flag)


def _acc_signal_kernel(x_ref, buf_ref, flag_ref, o_ref, stage_ref, oflag_ref,
                       cur_vmem, in_vmem, dsend, drecv, fsend, frecv, *,
                       axis: str, shift: int, axis_size: int,
                       offset: int, op: str, ordered: bool):
    my = jax.lax.axis_index(axis)
    target = jax.lax.rem(my + shift + axis_size, axis_size)
    pltpu.sync_copy(buf_ref, o_ref)
    data = pltpu.make_async_remote_copy(
        x_ref, stage_ref, dsend, drecv,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    data.start()
    if ordered:
        # P2: fence — the flag issues once the update's send is on the wire
        # behind it; no remote-completion round trip.
        data.wait_send()
    else:
        # Listing 1: full flush (remote completion) before the signal.
        data.wait()
    flag = pltpu.make_async_remote_copy(
        flag_ref, oflag_ref, fsend, frecv,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    flag.start()
    if ordered:
        data.wait_recv()  # my incoming update is staged
    # target side: fold the staged update into the window buffer before the
    # kernel exits — a consumer observing the flag sees the applied update
    n = x_ref.shape[0]
    pltpu.sync_copy(o_ref.at[pl.ds(offset, n)], cur_vmem)
    pltpu.sync_copy(stage_ref, in_vmem)
    cur_vmem[...] = combine_op(cur_vmem[...],
                               in_vmem[...].astype(cur_vmem.dtype), op)
    pltpu.sync_copy(cur_vmem, o_ref.at[pl.ds(offset, n)])
    flag.wait()


def accumulate_signal(update, buffer, flag, *, axis: str, axis_size: int,
                      shift: int = 1, op: str = "sum", offset: int = 0,
                      ordered: bool = True, config=None):
    """Fused accumulate+flag on the ring: every device accumulates ``update``
    into its neighbour's ``buffer`` at ``offset`` and raises ``flag`` there,
    in one lowered op.  Returns (updated_buffer, received_flag).

    Call inside ``shard_map``.  ``ordered=True`` is the paper's P2 path: the
    flag chains behind the update on the channel with no completion wait in
    between.  ``config``: optionally derive the ordering from a
    :class:`repro.core.rma.WindowConfig`, the same info object that drives
    the emulation layer's ``accumulate_signal``."""
    if op not in ATOMIC_KERNEL_OPS:
        raise ValueError(f"op {op!r} not in {ATOMIC_KERNEL_OPS} (the fused "
                         "kernel signals on the atomic path)")
    if op in ("band", "bor", "bxor") and not jnp.issubdtype(
            jnp.dtype(buffer.dtype), jnp.integer):
        raise ValueError(f"bitwise op {op!r} needs an integer buffer, "
                         f"got {buffer.dtype}")
    if config is not None:
        ordered = config.order
    out, _, oflag = pl.pallas_call(
        functools.partial(_acc_signal_kernel, axis=axis, shift=shift,
                          axis_size=axis_size, offset=offset, op=op,
                          ordered=ordered),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                   pl.BlockSpec(memory_space=pltpu.ANY),
                   pl.BlockSpec(memory_space=pltpu.ANY)],
        out_shape=[jax.ShapeDtypeStruct(buffer.shape, buffer.dtype),
                   jax.ShapeDtypeStruct(update.shape, update.dtype),
                   jax.ShapeDtypeStruct(flag.shape, flag.dtype)],
        scratch_shapes=[pltpu.VMEM(update.shape, buffer.dtype),
                        pltpu.VMEM(update.shape, update.dtype),
                        pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=interpret_mode(),
    )(update, buffer, flag)
    return out, oflag


__all__ = ["put_signal", "accumulate_signal"]
