"""One-sided ring all-reduce — P2-ordered RDMA chain as one Pallas kernel.

Reduce-scatter then all-gather, entirely with ``make_async_remote_copy``:
2(n−1) DMA hops per device, each chained behind the previous via its
semaphore pair — the kernel-level twin of
``repro.core.rma.collectives.rma_all_reduce(order=True)``.  Double-buffered
receive slots make hop *i+1*'s incoming transfer safe while hop *i*'s data
is still being consumed.

Layout: the per-device input is viewed as (n, chunk); after the kernel every
device holds the fully-reduced (n, chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _ar_kernel(x_ref, o_ref, recv_ref, acc_vmem, in_vmem, send_sem, recv_sem,
               credit_sem, *, axis: str, axis_size: int):
    n = axis_size
    my = jax.lax.axis_index(axis)
    nxt = jax.lax.rem(my + 1, n)
    prv = jax.lax.rem(my - 1 + n, n)

    # ---- reduce-scatter: n-1 hops --------------------------------------
    def rs_body(i, _):
        send_idx = jax.lax.rem(my - i + n * 8, n)
        recv_idx = jax.lax.rem(my - i - 1 + n * 8, n)
        slot = jax.lax.rem(i, 2)
        # flow control: the double-buffered landing zone tolerates one step
        # of ring skew; beyond that the sender must hold until the receiver
        # has drained the slot (the credit it signals below).  This is the
        # completion-vs-ordering machinery the paper's P2 reasons about —
        # per-hop *ordering* comes free on the chained channel, per-slot
        # *reuse* needs an explicit credit.
        @pl.when(i >= 2)
        def _hold():
            pltpu.semaphore_wait(credit_sem, 1)
        # send my current partial of chunk send_idx into neighbour's recv slot
        rdma = pltpu.make_async_remote_copy(
            o_ref.at[send_idx], recv_ref.at[slot], send_sem, recv_sem,
            device_id=(nxt,),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        rdma.wait()
        # accumulate the incoming partial into my chunk recv_idx
        # (HBM/ANY refs are DMA-only: stage through VMEM for the VPU add)
        pltpu.sync_copy(o_ref.at[recv_idx], acc_vmem)
        pltpu.sync_copy(recv_ref.at[slot], in_vmem)
        acc_vmem[...] = acc_vmem[...] + in_vmem[...]
        pltpu.sync_copy(acc_vmem, o_ref.at[recv_idx])
        # slot drained: credit my upstream so it may overwrite it
        pltpu.semaphore_signal(credit_sem, 1, device_id=prv,
                               device_id_type=pltpu.DeviceIdType.MESH)
        return 0

    # initialize output with my own contribution
    pltpu.sync_copy(x_ref, o_ref)
    jax.lax.fori_loop(0, n - 1, rs_body, 0)
    # drain outstanding credits so the semaphore ends at zero
    pltpu.semaphore_wait(credit_sem, 2 if n > 2 else 1)

    # ---- all-gather: n-1 hops -------------------------------------------
    # after RS, my fully-reduced chunk is (my+1) % n
    def ag_body(i, _):
        send_idx = jax.lax.rem(my + 1 - i + n * 8, n)
        rdma = pltpu.make_async_remote_copy(
            o_ref.at[send_idx], o_ref.at[send_idx], send_sem, recv_sem,
            device_id=(nxt,),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        rdma.wait()
        return 0

    jax.lax.fori_loop(0, n - 1, ag_body, 0)


def ring_all_reduce(x, *, axis: str, axis_size: int, config=None):
    """All-reduce-sum ``x`` (leading dim divisible by axis_size) across the
    ring.  Call inside ``shard_map``; returns the reduced array.

    ``config``: optionally validate against a
    :class:`repro.core.rma.WindowConfig`.  This kernel *is* the P2-ordered
    channel (hops chain on semaphore pairs with no per-hop completion ack),
    so a window config that did not declare ``order=True`` must not be
    lowered to it — the emulation layer's ``rma_all_reduce(order=False)``
    is the faithful fallback."""
    if config is not None and not config.order:
        raise ValueError(
            "ring_all_reduce is the mpi_win_order=true fast path; the "
            "supplied WindowConfig declares order=False — use "
            "repro.core.rma.rma_all_reduce(order=False) for the flush-"
            "separated baseline")
    n = axis_size
    orig = x.shape[0]
    pad = (-orig) % n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    chunk = x.shape[0] // n
    xview = x.reshape((n, chunk) + x.shape[1:])
    out, _ = pl.pallas_call(
        functools.partial(_ar_kernel, axis=axis, axis_size=axis_size),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                   pl.BlockSpec(memory_space=pltpu.ANY)],
        # the (2, chunk) double-buffered receive landing zone is a second
        # output rather than scratch: remote DMA needs it in ANY/HBM space
        out_shape=[jax.ShapeDtypeStruct(xview.shape, x.dtype),
                   jax.ShapeDtypeStruct((2, chunk) + x.shape[1:], x.dtype)],
        scratch_shapes=[pltpu.VMEM((chunk,) + x.shape[1:], x.dtype),
                        pltpu.VMEM((chunk,) + x.shape[1:], x.dtype),
                        pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR],
        interpret=interpret_mode(),
    )(xview)
    out = out.reshape((-1,) + x.shape[1:])
    return out[:orig] if pad else out


__all__ = ["ring_all_reduce"]
