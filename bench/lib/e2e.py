"""The end-to-end arithmetic and the peak table."""
from __future__ import annotations

import numpy as np

from bench.lib import common


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear between order statistics)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, float), q))


def peak(kind: str) -> dict:
    """The chip's published peaks; a chip not in the table is an error."""
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise common.BenchError(f"no peaks for device kind {kind!r} in "
                                f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def ttft(reqs) -> list[float]:
    """First token seen by the harness minus the time it was due."""
    return [r["t_tokens"][0] - r["due_abs"] for r in reqs]


def itl(reqs, until: float) -> list[float]:
    """Every gap between consecutive tokens of every request, both seen by
    ``until`` (the window's close)."""
    return [b - a for r in reqs for a, b in zip(r["t_tokens"], r["t_tokens"][1:])
            if b <= until]
