"""What the per-layer metric readers share: the steps the traced slice
holds, and FLOP and byte sums over them."""
from __future__ import annotations

from bench.lib import flops


def traced_steps(record: dict) -> list:
    """The harness's steps that ran wholly inside the traced slice."""
    on, off = record["traced"]
    if on is None:
        return []
    return [s for s in record["steps"] if s[0] >= on and s[1] <= off]


def step_flops(cfg: dict, steps) -> float:
    """Model operations of every prefill and decoded token in ``steps``."""
    return sum(sum(flops.prefill_flops(cfg, n) for n in pre)
               + flops.decode_flops(cfg, ctx) for _, _, pre, ctx in steps)


def share(part: float, whole: float):
    """``part / whole`` in percent; nothing where there is nothing to read."""
    if part <= 0 or whole <= 0:
        return None
    return 100.0 * part / whole
