"""The control comes out as not correct: the reference in float8, put in
the program's place, reads above the cell's limit.  At a small size on
the CPU; ``bench/calibrate.py --control`` takes the same readings on the
chip at the cells' own sizes."""
import time

import jax
import pytest

from bench import calibrate
from bench import run as bench_run
from bench.lib import common, serve
from bench.tests import small

# float8 rounding flips the argmax only where rows are wide and heads are
# 128 wide, as in the cell; the smallest widths leave every token alone
SERVE_WIDTHS = dict(hidden_size=256, intermediate_size=1024, num_attention_heads=2,
                    num_key_value_heads=1, num_hidden_layers=4, vocab_size=2048)

def test_serving_control_fails_the_limit(monkeypatch):
    monkeypatch.setattr(serve, "check", serve.check)   # restored afterwards
    readings = {}
    calibrate.watch_control(readings)
    out = small.run("sc2-code-open", **SERVE_WIDTHS)
    limit = out["checks"]["served_gap_sigma"]["limit"]
    assert out["checks"]["served_gap_sigma"]["value"] <= limit
    assert readings["control"]["served_gap_sigma"] > limit


@pytest.mark.parametrize("seed", [61, 2**31 + 5])
def test_training_control_fails_a_limit(seed):
    cell = "sc2-train-rma-dp4"
    work, cfg = small.workload(cell), small.config(common.workload(cell)["config"])
    ctx = bench_run.Context(cell, seed, 1.0, False, work, cfg, jax.devices()[:1],
                            common.CompileCounter(), time.perf_counter(),
                            common.TRACE_DIR / cell)
    ctl = calibrate.train_control(common.driver(work["driver"]), ctx)
    assert any(v > work["limits"][k] for k, v in ctl.items()), ctl
