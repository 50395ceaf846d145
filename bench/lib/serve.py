"""Serving cells: build the engine on seeded weights, warm up the cell's
shapes, watch tokens come out of ``ServeEngine.step``, and check a sample
of the finished requests against the float32 reference."""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from bench.lib import common, program, reference, weights

clock = time.perf_counter


class Served:
    """Per-request timing as the harness sees it: a token arrives when the
    ``ServeEngine.step`` that made it returns."""

    def __init__(self, reqs, page_tokens: int):
        self.by_rid = {r["rid"]: r for r in reqs}
        for r in reqs:
            r["t_tokens"] = []
            r["tokens"] = None
        self._done_seen = 0
        self.page_tokens = page_tokens
        self.steps = []        # (t0, t1, prefill lengths, decode contexts)
        # the most, after any step: slots live, pages reserved, pages holding tokens
        self.peak = {"live_slots": 0, "pages_reserved": 0, "pages_with_tokens": 0}

    def observe(self, eng, t0: float, t1: float) -> list:
        """Record what one step produced; returns the requests it finished."""
        prefills, contexts, finished = [], [], []
        held = 0

        def take(rid, toks, live):
            nonlocal held
            r = self.by_rid.get(rid)
            if r is None:
                return
            new = len(toks) - len(r["t_tokens"])
            if not r["t_tokens"] and new > 0:
                r["t_first_step"] = t0
                prefills.append(len(r["prompt"]))
            if len(toks) >= 2 and new > 0:
                contexts.append(len(r["prompt"]) + len(toks) - 2)
            r["t_tokens"].extend([t1] * new)
            if live:
                held += -(-(len(r["prompt"]) + len(toks)) // self.page_tokens)
            else:
                r["tokens"] = list(toks)
                finished.append(r)

        for slot, req in eng.slot_req.items():
            take(req.rid, eng.slot_generated[slot], True)
        for c in eng.done[self._done_seen:]:
            take(c.rid, c.tokens, False)
        self._done_seen = len(eng.done)
        self.steps.append((t0, t1, prefills, contexts))
        use = {"live_slots": len(eng.slot_req),
               "pages_reserved": program.pages_reserved(eng)[0],
               "pages_with_tokens": held}
        self.peak = {k: max(v, use[k]) for k, v in self.peak.items()}
        return finished


def build(ctx):
    """Model, seeded weights on the chip, engine."""
    import jax

    run = ctx.cfg["serving"]
    mc = program.model_config(ctx.cfg, run)
    model = program.build_model(mc)
    sharding = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    params = weights.make(program.param_shapes(model), ctx.seed,
                          ctx.cfg["initializer_range"], run["param_dtype"],
                          sharding)
    jax.block_until_ready(params)
    eng = program.serve_engine(model, params, run)
    return mc, params, eng


def warm_up(eng, lengths, vocab: int, n_slots: int) -> None:
    """Compile every prefill length the traffic sends and the decode step,
    and admit into every slot once (slot teardown runs too)."""
    rng = np.random.default_rng(0)
    lens = list(lengths) + [min(lengths)] * max(0, n_slots - len(lengths))
    for i, n in enumerate(lens):
        eng.submit(program.request(-1 - i, rng.integers(0, vocab, n, dtype=np.int32), 2))
    eng.run(strict=True)
    eng.done.clear()


def has_work(eng, until: float) -> bool:
    """Whether the engine has work; if not, sleep a little towards
    ``until``."""
    if eng.scheduler.pending_count or eng.slot_req:
        return True
    with common.span("bench.wait_due"):
        time.sleep(max(0.0, min(until - clock(), 0.002)))
    return False


def sample(finished, seed: int, target: int) -> list:
    """A sample drawn from the seed with the longest finished request in
    it, until it holds ``target`` served tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r["tokens"]), len(r["prompt"])))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng(int(seed) % 2**64 + 7).permutation(len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n >= target:
            break
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def check(params, cfg: dict, picked, n_max: int, lowp: bool = False) -> np.ndarray:
    """The served-token gaps (in row standard deviations) of ``picked``
    against the float32 reference; with ``lowp`` the float8 control's."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(reference.served_gaps, cfg=cfg, lowp=lowp))
    gaps = []
    for r in picked:
        p, toks = len(r["prompt"]), r["tokens"]
        seq = np.concatenate([r["prompt"], np.asarray(toks[:-1], np.int32)])
        s = -(-len(seq) // 1024) * 1024
        seq = np.pad(seq, (0, s - len(seq)))
        n = len(toks)
        at = np.full(n_max, p - 1, np.int32)
        at[:n] = p - 1 + np.arange(n)
        served = np.full(n_max, toks[0], np.int32)
        served[:n] = toks
        g = fn(params, jnp.asarray(seq)[None], jnp.asarray(at), jnp.asarray(served))
        gaps.append(np.asarray(g)[:n])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def verify(ctx, params, served_reqs, n_max: int) -> dict:
    """The reference over a sample of the finished requests (call it once
    the peak memory is read and the engine is freed)."""
    gc.collect()
    finished = [r for r in served_reqs if r["tokens"] is not None]
    picked = sample(finished, ctx.seed, ctx.work["check_tokens"])
    t0 = clock()
    gaps = check(params, ctx.cfg, picked, n_max)
    common.note(f"[check] {len(picked)} requests, {len(gaps)} served tokens "
                f"against the float32 reference in {clock() - t0:.3f} s; "
                f"{int((gaps == 0).sum())} are its argmax")
    worst = float(gaps.max()) if len(gaps) else float("inf")
    limit = ctx.work["limits"]["served_gap_sigma"]
    return {"served_gap_sigma": {"value": worst, "limit": limit}}
