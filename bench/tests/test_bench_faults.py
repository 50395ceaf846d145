"""A run with the timed path broken underneath comes out not correct: a
served token altered where it is made; a train step that returns its
state unchanged, leaves half of the batch out, or leaves the exchange
between chips out.  The chip check is skipped; everything else of the
run is driven at a small size on the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import common
from bench.tests import small


def test_serving_unbroken_is_correct():
    out = small.run("sc2-code-open")
    assert out["correct"] and out["failed"] == 0


def test_serving_token_altered_is_not_correct(monkeypatch):
    common.use_program()
    from repro.serve.engine import Executor

    decode = Executor.decode

    def altered(self, last_tokens):
        out = np.array(decode(self, last_tokens))
        out[0] = (out[0] + 1) % self.model.cfg.vocab
        return out

    monkeypatch.setattr(Executor, "decode", altered)
    out = small.run("sc2-code-open")
    assert not out["correct"]
    assert out["checks"]["served_gap_sigma"]["value"] > out["checks"]["served_gap_sigma"]["limit"]


@pytest.fixture(scope="module")
def train_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(common.BENCH / "tests" / "faults_main.py"),
                        "frozen", "half", "exchange"],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return {r["fault"]: r for r in (json.loads(line[6:]) for line in p.stdout.splitlines()
                                    if line.startswith("FAULT "))}


def test_train_unbroken_gradients_within_limits(train_runs):
    checks = train_runs[None]["checks"]
    for k in ("loss_rel_gap", "grad_norm_gap"):
        assert checks[k]["value"] <= checks[k]["limit"]


@pytest.mark.parametrize("fault", ["frozen", "half", "exchange"])
def test_train_fault_is_not_correct(train_runs, fault):
    assert train_runs[fault]["correct"] is False
