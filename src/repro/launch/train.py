"""Training launcher: data → step → checkpoint/restart → straggler watch.

Runs real training under ``jax.jit`` on the default device: the step is
not sharded, so on a multi-chip host it runs on the first chip (only
``--moe-ep=rma`` spreads the experts over every device).  The data-parallel
RMA gradient sync across chips is built with ``make_train_step(grad_sync=
"rma_ring")`` under ``shard_map`` (see ``chip_smoke.py --four-chips``).
Fault-tolerance behaviours:

* periodic async checkpoints (atomic, retained K);
* ``--resume`` restores the latest complete checkpoint **and** the data
  pipeline position (deterministic counter-based batches);
* a straggler monitor EMA-watches step times; chronic stragglers raise (the
  cluster layer restarts the job on a healthy slice — simulated in tests);
* simulated failure injection (``--fail-at-step``) for the restart test.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --tiny \
      --steps 200 --ckpt-dir /tmp/ckpt --resume
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.tiny import tiny_config
from repro.data.pipeline import DataConfig, make_source
from repro.ft.straggler import StragglerMonitor
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.trainstep import make_train_step


@dataclasses.dataclass
class TrainRun:
    """Result record for tests/examples."""
    steps_run: int
    final_step: int
    losses: list
    straggler_events: int


def train(arch: str, *, tiny: bool = True, steps: int = 100,
          global_batch: int = 8, seq_len: int = 64,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          resume: bool = False, fail_at_step: int | None = None,
          peak_lr: float = 3e-3, log_every: int = 10,
          data_seed: int = 0, grad_sync: str = "gspmd",
          moe_ep: str | None = None) -> TrainRun:
    cfg = tiny_config(arch) if tiny else get_config(arch)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(peak_lr=peak_lr, warmup_steps=min(20, steps // 5),
                              total_steps=steps)
    data = make_source(DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                                  global_batch=global_batch, seed=data_seed))

    params = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    start_step = 0

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if resume and mgr is not None:
        latest = mgr.latest_step()
        if latest is not None:
            state = mgr.restore(latest, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start_step = latest
            print(f"[train] resumed from step {latest}", flush=True)

    step_fn = jax.jit(make_train_step(model, opt_cfg, grad_sync=grad_sync,
                                      moe_ep=moe_ep))
    monitor = StragglerMonitor(threshold=3.0)
    losses = []

    # moe_ep="rma" dispatches through shard_map over the expert axis, which
    # only exists while sharding rules are active — without this the flag
    # would silently trace the degenerate single-device path on a multi-
    # device host.  Rules stay scoped to this run's tracing.
    rules_ctx = contextlib.nullcontext()
    if moe_ep == "rma":
        from repro import compat, sharding

        n_dev = len(jax.devices())
        if n_dev > 1 and cfg.moe is not None and cfg.moe.num_experts % n_dev == 0:
            rules_ctx = sharding.use_rules(compat.make_mesh((n_dev,), ("model",)))
            print(f"[train] moe_ep=rma: expert axis over {n_dev} devices",
                  flush=True)
        else:
            print(f"[train] moe_ep=rma: single-device fallback "
                  f"({n_dev} devices, {cfg.moe.num_experts if cfg.moe else 0} "
                  "experts)", flush=True)

    with rules_ctx:
        return _train_loop(start_step, steps, data, step_fn, params, opt_state,
                           monitor, losses, mgr, ckpt_every, fail_at_step,
                           log_every)


def _train_loop(start_step, steps, data, step_fn, params, opt_state, monitor,
                losses, mgr, ckpt_every, fail_at_step, log_every) -> TrainRun:
    for step in range(start_step, steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        if fail_at_step is not None and step == fail_at_step:
            if mgr is not None:
                # the preemption notice's grace period: let the in-flight
                # async checkpoint land before the process dies, so the
                # latest completed save is durable
                mgr.wait()
            raise RuntimeError(f"simulated preemption at step {step}")
        monitor.start()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        monitor.stop(step)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step={step} loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr is not None:
        mgr.save(steps, {"params": params, "opt": opt_state}, blocking=True)
    return TrainRun(steps_run=steps - start_step, final_step=steps,
                    losses=losses, straggler_events=len(monitor.events))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--full", dest="tiny", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--moe-ep", choices=("gspmd", "rma"), default=None,
                    help="MoE expert-parallel dispatch: partitioner all-to-all"
                         " (gspmd) or the one-sided RMA token exchange (rma)")
    args = ap.parse_args(argv)
    use_compile_cache()
    run = train(args.arch, tiny=args.tiny, steps=args.steps,
                global_batch=args.global_batch, seq_len=args.seq_len,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                resume=args.resume, fail_at_step=args.fail_at_step,
                peak_lr=args.peak_lr, moe_ep=args.moe_ep)
    print(f"[train] done: loss {run.losses[0]:.4f} -> {run.losses[-1]:.4f}, "
          f"stragglers={run.straggler_events}")


if __name__ == "__main__":
    main()
