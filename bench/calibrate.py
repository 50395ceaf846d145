#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, many seeds in one process.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10 \
      [--control] [--fault half|exchange|frozen] [--rates 4,6,8]

For each seed it runs the cell as ``bench/run.py`` does (shorter window)
and prints one JSON line with the numbers compared.  ``--control`` adds
the control's numbers on the same prompts or batches: the reference in
float8 put in the program's place.  ``--fault`` plants a fault in the
program's train step (half of each device's batch left out, the
gradient exchange left out, or the state returned unchanged).  ``--rates`` sweeps an open-loop cell's
arrival rate, with no correctness check.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as bench_run  # noqa: E402
from bench.lib import common, program, serve  # noqa: E402


def plant(fault: str) -> None:
    """Break the program's train step the named way."""
    make = program.train_step

    def broken(model, opt_cfg, *, grad_sync, n):
        if fault == "exchange":
            return make(model, opt_cfg, grad_sync="gspmd", n=n)
        step = make(model, opt_cfg, grad_sync=grad_sync, n=n)
        if fault == "half":
            return lambda p, o, b: step(p, o, {k: v[:v.shape[0] // 2] for k, v in b.items()})
        if fault == "frozen":
            return lambda p, o, b: (p, o, step(p, o, b)[2])
        raise ValueError(f"unknown fault {fault!r}")

    program.train_step = broken


def train_control(drv, ctx) -> dict:
    """The training control on one chip: the float8 reference and the
    float32 one from the seed's weights and batches (the program does not
    run: its loaded step leaves no room for the float8 program's 5.4 GB,
    which also goes first, onto an empty chip)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    tcfg = ctx.cfg["training"]
    model = program.build_model(program.model_config(ctx.cfg, tcfg))
    dev = SingleDeviceSharding(ctx.devices[0])
    params, pool = drv.seeded_state(ctx, model, dev, dev)
    p0 = jax.device_get(params)
    batches = np.asarray(jax.device_get(pool[:ctx.work["check_steps"]]))
    del params, pool
    ctl = drv.follow(ctx, p0, batches, lowp=True)
    jax.clear_caches()
    gc.collect()
    ref = drv.follow(ctx, p0, batches)
    return {k: c["value"] for k, c in drv.compare(ctl, ref, ctx.work["limits"]).items()}


def watch_control(readings: dict) -> None:
    """Make the serving check also read the control."""
    check = serve.check

    def both(params, cfg, picked, n_max, lowp=False):
        gaps = check(params, cfg, picked, n_max)
        ctl = check(params, cfg, picked, n_max, lowp=True)
        readings["control"] = {"served_gap_sigma": float(ctl.max())}
        return gaps

    serve.check = both


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    ap.add_argument("--rates", help="open-loop rates to sweep (no check)")
    args = ap.parse_args()
    work = common.workload(args.workload)
    cfg = common.config(work["config"])
    cell = {"name": args.workload, "chips": work["chips"]}

    import jax

    common.place_compile_cache()
    drv = common.driver(work["driver"])
    train_ctl = args.control and hasattr(drv, "follow")
    devices = common.require_devices(1 if train_ctl else cell["chips"])
    if args.fault:
        plant(args.fault)
    counter = common.CompileCounter()
    if args.rates:
        serve.verify = lambda ctx, params, reqs, n_max: {}
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    runs = [(s, r) for r in rates for s in (int(x) for x in args.seeds.split(","))]
    for seed, rate in runs:
        if rate is not None:
            work = dict(work, rate_per_s=rate)
        readings: dict = {}
        ctx = bench_run.Context(cell["name"], seed, args.seconds, False, work, cfg,
                                devices, counter, time.perf_counter(),
                                common.TRACE_DIR / cell["name"])
        if train_ctl:
            print("READING " + json.dumps({"seed": seed, "control": train_control(drv, ctx)}),
                  flush=True)
            jax.clear_caches()
            continue
        if args.control:
            watch_control(readings)
        out = drv.run(ctx)
        line = {"seed": seed, "rate": rate, "fault": args.fault, "correct": out["correct"],
                "program": {k: c["value"] for k, c in out["checks"].items()},
                **readings, "e2e": out["e2e"]}
        print("READING " + json.dumps(line), flush=True)
        serve.check = SERVE_CHECK   # undo the wrapping before the next seed
        del out
        jax.clear_caches()          # jitted closures hold the last engine
        gc.collect()


SERVE_CHECK = serve.check

if __name__ == "__main__":
    main()
