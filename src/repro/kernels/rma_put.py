"""One-sided put — the window layer's hot path as a real TPU kernel.

``pltpu.make_async_remote_copy`` issues an ICI remote DMA: the origin writes
directly into the target device's buffer; the target TensorCore is not
involved (the paper's "intrinsic to the origin" property, §2.3 fn.1).
Completion is tracked by DMA semaphores — the hardware analogue of the
window layer's per-stream tokens:

* ``rdma.start()``  ≙ ``Window.put`` (issue; returns immediately)
* ``rdma.wait()``   ≙ ``Window.flush(stream)`` for this stream —
  **thread-scope** completion (P1): it waits only this DMA's semaphores,
  not every outstanding transfer of the device.

Validated cross-device in the Mosaic interpreter (tests/test_kernels.py);
ref oracle: ``repro.kernels.ref.ring_put_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _put_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str, shift: int,
                axis_size: int):
    my = jax.lax.axis_index(axis)
    target = jax.lax.rem(my + shift + axis_size, axis_size)
    rdma = pltpu.make_async_remote_copy(
        x_ref, o_ref, send_sem, recv_sem,
        device_id=(target,),
        device_id_type=pltpu.DeviceIdType.MESH)
    rdma.start()
    rdma.wait()  # thread-scope flush: this stream's semaphores only


def ring_put(x, *, axis: str, axis_size: int, shift: int = 1):
    """Every device puts its shard into its ring neighbour's window.

    Call inside ``shard_map`` over ``axis``.  Returns the received buffer
    (what the neighbour put into *this* device's window).
    """
    return pl.pallas_call(
        functools.partial(_put_kernel, axis=axis, shift=shift,
                          axis_size=axis_size),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=interpret_mode(),
    )(x)


__all__ = ["ring_put"]
