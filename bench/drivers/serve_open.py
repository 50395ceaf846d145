"""Open loop: requests are submitted when due, whatever the engine is
doing; time to first token counts from when each was due."""
from __future__ import annotations

import numpy as np

from bench.lib import common, e2e, program, serve, traffic

clock = serve.clock
DRAIN_S = 60.0


def run(ctx) -> dict:
    mc, params, eng = serve.build(ctx)
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
                f"slots live, {use['pages_reserved']} of {pages} KV pages reserved "
                f"(a slot's {run_cfg['max_seq']} positions at admission), "
                f"{use['pages_with_tokens']} holding tokens")
    checks = serve.verify(ctx, params, reqs, ctx.work["output"]["max"])
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
