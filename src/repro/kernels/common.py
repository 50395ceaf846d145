"""Shared kernel utilities: interpret-mode selection, the accumulate op
table, and tiling helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def interpret_mode():
    """TPU → compiled Mosaic; anything else → the Mosaic TPU interpreter.

    The interpreter executes the kernel body (including semaphores and
    cross-device remote DMA) in Python with simulated shared memory, which is
    how every kernel here is validated on CPU against its ref.py oracle.
    """
    if jax.default_backend() == "tpu":
        return False
    # eager DMA execution models hardware (transfers land when posted);
    # the default "on_wait" defers execution to the wait and breaks
    # multi-hop ring schedules
    return pltpu.InterpretParams(dma_execution_mode="eager")


#: Ops the NIC-atomic-style kernels implement — the accumulate subset of the
#: hardware envelope (repro.core.rma.intrinsic.INTRINSIC_OPS minus the
#: non-accumulate cas/no_op entries).
ATOMIC_KERNEL_OPS = ("sum", "min", "max", "replace", "band", "bor", "bxor")


def combine_op(cur, upd, op: str):
    """Element-wise combine — THE accumulate op table.  Shared by all the
    kernels (atomic twins in kernels/intrinsic.py and the fused
    accumulate+signal, the tiled VPU kernel in kernels/accumulate.py) and,
    via ``repro.core.rma.accumulate.apply_op``, by the HLO-emulation paths,
    so the two layers cannot drift.  ``prod`` is tiled-only (NICs don't
    multiply): ``ATOMIC_KERNEL_OPS`` is the whitelist the atomic kernels
    enforce before reaching here."""
    if op == "sum":
        return cur + upd
    if op == "min":
        return jnp.minimum(cur, upd)
    if op == "max":
        return jnp.maximum(cur, upd)
    if op == "prod":
        return cur * upd
    if op in ("band", "bor", "bxor"):
        return {"band": cur & upd, "bor": cur | upd, "bxor": cur ^ upd}[op]
    if op == "replace":
        return upd
    raise ValueError(f"unsupported accumulate op {op!r}")


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


__all__ = ["interpret_mode", "cdiv", "round_up", "combine_op",
           "ATOMIC_KERNEL_OPS"]
