#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's workload file (``bench/workloads/<name>.json``) names its
configuration (``bench/configs/``), its chips and its loop
(``bench/drivers/``); ``BENCHMARK.json`` says which metrics it reports.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a slice of the window is traced and the result carries its
per-layer metrics (one reader each in ``bench/metrics/``).  The last line
of standard output is the result; the numbers the correctness check
compared, each beside its limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.lib import common  # noqa: E402


@dataclasses.dataclass
class Context:
    name: str
    seed: int
    seconds: float
    trace: bool
    work: dict
    cfg: dict
    devices: list
    counter: object
    t_start: float
    trace_dir: Path


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def per_layer(bm: dict, ctx: Context, out: dict) -> tuple[dict, dict, dict]:
    """Per-layer metrics, device times and breakdown of a traced run."""
    from bench.lib import e2e, trace as tr

    t = tr.load(tr.find(ctx.trace_dir))
    out["record"]["peak"] = e2e.peak(ctx.devices[0].device_kind)
    metrics = {}
    for m in bm["per_layer"]:
        if applies(m, ctx.name):
            value = common.metric_reader(m["name"]).read(t, out["record"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = min(t.devices)
    device = {"busy_s": t.mean_busy_s(), "window_s": t.window_s}
    breakdown = {"device_ops": t.top_ops(dev), "idle_gaps": t.idle_gaps(dev)}
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return metrics, device, breakdown


def main(argv=None, *, need_chip: bool = True, cfg: dict | None = None,
         work: dict | None = None) -> dict:
    """One run.  Tests pass ``need_chip=False`` and small ``cfg`` and
    ``work`` in place of the cell's files."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = common.benchmark()
    name = args.workload
    work = work or common.workload(name)
    cfg = cfg or common.config(work["config"])

    import jax

    common.place_compile_cache()
    devices = (common.require_devices(work["chips"]) if need_chip
               else jax.devices()[:work["chips"]])
    trace_dir = common.TRACE_DIR / name
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(name, args.seed, args.seconds, bool(args.trace), work, cfg,
                  devices, common.CompileCounter(), T_START, trace_dir)
    out = common.driver(work["driver"]).run(ctx)

    result = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    device = common.device_record(devices)
    device["memory_peak_bytes"] = int(out["memory_peak_bytes"])
    if args.trace:
        metrics, dev_times, breakdown = per_layer(bm, ctx, out)
        device.update(dev_times)
    else:
        metrics = {}
        for m in bm["end_to_end"]:
            if applies(m, ctx.name):
                metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = out["checks"]
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
