"""Open loop for a DeepSeek-V2 configuration (latent attention, an expert
share): the loop of ``serve_open``, with the program's model configuration
taken from ``program_mla`` and the served tokens checked against
``reference_mla``.  Requests are submitted when due, whatever the engine
is doing; time to first token counts from when each was due."""
from __future__ import annotations

import functools
import gc

import numpy as np

from bench.lib import common, e2e, program, program_mla, reference_mla, serve, traffic, weights

clock = serve.clock
DRAIN_S = 60.0
CHECK_PAD = 1024      # checked sequences are padded to a power of two times this


def build(ctx):
    """Model, seeded weights on the chip, engine."""
    import jax

    run = ctx.cfg["serving"]
    mc = program_mla.model_config(ctx.cfg, run)
    model = program.build_model(mc)
    sharding = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    params = weights.make(program.param_shapes(model), ctx.seed,
                          ctx.cfg["initializer_range"], run["param_dtype"],
                          sharding)
    jax.block_until_ready(params)
    return mc, params, program.serve_engine(model, params, run)


def check(params, cfg: dict, picked, n_max: int, lowp: bool = False) -> np.ndarray:
    """The served-token gaps (in row standard deviations) of ``picked``
    against the float32 reference; with ``lowp`` the float8 control's."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(reference_mla.served_gaps, cfg=cfg, lowp=lowp))
    gaps = []
    for r in picked:
        p, toks = len(r["prompt"]), r["tokens"]
        seq = np.concatenate([r["prompt"], np.asarray(toks[:-1], np.int32)])
        s = CHECK_PAD * 2 ** int(np.ceil(np.log2(-(-len(seq) // CHECK_PAD))))
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
    picked = serve.sample(finished, ctx.seed, ctx.work["check_tokens"])
    t0 = clock()
    gaps = check(params, ctx.cfg, picked, n_max)
    common.note(f"[check] {len(picked)} requests, {len(gaps)} served tokens "
                f"against the float32 reference in {clock() - t0:.3f} s; "
                f"{int((gaps == 0).sum())} are its argmax")
    worst = float(gaps.max()) if len(gaps) else float("inf")
    limit = ctx.work["limits"]["served_gap_sigma"]
    return {"served_gap_sigma": {"value": worst, "limit": limit}}


def run(ctx) -> dict:
    mc, params, eng = build(ctx)
    run_cfg = ctx.cfg["serving"]
    serve.warm_up(eng, traffic.lengths_used(ctx.work), mc.vocab, run_cfg["n_slots"])
    reqs = traffic.open_loop(ctx.work, ctx.seed, ctx.seconds, mc.vocab)
    served = serve.Served(reqs, run_cfg["page_tokens"])
    setup_s = clock() - ctx.t_start
    compiles0 = ctx.counter.count

    t_w = clock()
    end = t_w + ctx.seconds
    for r in reqs:
        r["due_abs"] = t_w + r["due"]
    prof = common.TraceSlice(ctx, t_w)
    late, i, n = [], 0, len(reqs)

    def submit(now):
        nonlocal i
        with common.span("bench.submit"):
            while i < n and reqs[i]["due_abs"] <= now:
                r = reqs[i]
                eng.submit(program.request(r["rid"], r["prompt"], r["max_new"]))
                late.append(now - r["due_abs"])
                i += 1

    def step():
        t0 = clock()
        with common.span("bench.step"):
            eng.step()
        served.observe(eng, t0, clock())

    while True:
        now = clock()
        prof.tick(now)
        if now >= end:
            break
        submit(now)
        if serve.has_work(eng, min(reqs[i]["due_abs"] if i < n else end, end)):
            step()
    prof.close()
    in_window = ctx.counter.count - compiles0
    backlog = eng.scheduler.pending_count
    use, pages = dict(served.peak), program.pages_reserved(eng)[1]
    sums = eng.stats()
    # every request due in the window gets its first token (at most a minute)
    t_stop = clock() + DRAIN_S
    while clock() < t_stop and not all(r["t_tokens"] for r in reqs):
        submit(clock())
        if eng.scheduler.pending_count or eng.slot_req:
            step()
    peak = common.peak_memory(ctx.devices)
    eng = None

    got = [r for r in reqs if r["t_tokens"]]
    itl = e2e.itl(got, end)
    ttft = e2e.ttft(got)
    common.note(f"[window] {n} requests due, {len(got)} with a first token, "
                f"backlog at close {backlog}, programs compiled in the window "
                f"{in_window}, generator lateness p50 {e2e.percentile(late, 50):.6f} s "
                f"max {max(late):.6f} s, {len(served.steps)} steps, "
                f"{eng_tokens(got, end)} tokens in the window, TTFT p50/p90/p95/max "
                f"{[round(e2e.percentile(ttft, q), 6) for q in (50, 90, 95, 100)]} s, "
                f"ITL mean {1e3 * np.mean(itl):.3f} p50/p90/p95/p99 "
                f"{[round(1e3 * e2e.percentile(itl, q), 3) for q in (50, 90, 95, 99)]} ms "
                f"over {len(itl)} gaps")
    common.note(f"[pool] most in the window: {use['live_slots']} of {run_cfg['n_slots']} "
                f"slots live, {use['pages_reserved']} of {pages} latent pages reserved "
                f"(a slot's {run_cfg['max_seq']} positions at admission), "
                f"{use['pages_with_tokens']} holding tokens")
    common.note("[experts] to the window's close, warm-up included: "
                + ", ".join(f"{k} {sums[k]}" for k in sorted(sums)
                            if k.startswith(("prefill_", "decode_"))))
    checks = verify(ctx, params, reqs, ctx.work["output"]["max"])
    return {
        "correct": common.checks_pass(checks) and len(got) == n,
        "attempted": n, "failed": n - len(got),
        "e2e": {"setup_s": setup_s,
                "ttft_p90_s": e2e.percentile(ttft, 90),
                "itl_mean_ms": 1e3 * float(np.mean(itl))},
        "checks": checks, "memory_peak_bytes": peak,
        "record": {"cfg": ctx.cfg, "steps": served.steps,
                   "traced": (prof.t_on, prof.t_off),
                   "queue_waits": [r["t_first_step"] - r["due_abs"] for r in got]},
    }


def eng_tokens(got, end: float) -> int:
    return int(sum(np.sum(np.asarray(r["t_tokens"]) <= end) for r in got))
