"""Seeded traffic from a workload file's parameters.

The schedule (every request's prompt length, output length and due time,
in order) is drawn once from the file's ``schedule_seed``: every run
replays the same trace.  The run's ``--seed`` draws the token ids.  A
tail over some seventy requests of a bursty schedule moves by half from
one order of the same sizes and gaps to another, so with the order left
to the seed the spread between seeds would be the spread of the work,
not of the system.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % 2**64)


def sizes(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``spec``: ``lognormal`` (median, sigma) or
    ``loguniform``, clipped to [min, max], rounded to the nearest of
    ``buckets`` when it has them."""
    lo, hi = spec["min"], spec["max"]
    if spec["kind"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif spec["kind"] == "loguniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
    else:
        raise ValueError(f"unknown size distribution {spec['kind']!r}")
    x = np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec.get("buckets"):
        b = np.asarray(spec["buckets"])
        x = b[np.abs(x[:, None] - b[None, :]).argmin(1)]
    return x


def gaps(spec: dict, n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of mean ``1/rate``: ``gamma`` with the
    given coefficient of variation (CV 1 is Poisson)."""
    if spec["kind"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    cv2 = spec["cv"] ** 2
    return rng.gamma(1.0 / cv2, cv2 / rate, n)


def open_loop(work: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """Requests with a due time each: ``rate_per_s * seconds`` of them,
    all due inside ``[0, seconds)``."""
    n = max(2, round(work["rate_per_s"] * seconds))
    base = _rng(work["schedule_seed"])
    prompt = sizes(work["prompt"], n, base)
    output = sizes(work["output"], n, base)
    g = gaps(work["arrival"], n, work["rate_per_s"], base)
    due = np.concatenate([[0.0], np.cumsum(g * (seconds / g.sum()))[:-1]])
    run = _rng(seed)
    return [{"rid": i, "due": float(t), "max_new": int(o),
             "prompt": run.integers(0, vocab, int(p), dtype=np.int32)}
            for i, (p, o, t) in enumerate(zip(prompt, output, due))]


def lengths_used(work: dict) -> list[int]:
    """The prompt lengths this traffic can send (what warm-up compiles)."""
    p = work["prompt"]
    if p.get("buckets"):
        return sorted(p["buckets"])
    raise ValueError("serving traffic needs prompt buckets: the executor "
                     "compiles one prefill per prompt length")
