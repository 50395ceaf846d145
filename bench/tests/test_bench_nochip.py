"""Without a TPU a run fails and prints no result; so does a directory that
holds only BENCHMARK.json and the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import common


def _run(cwd, cell="sc2-code-open"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "2147483999",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


@pytest.mark.parametrize("cell", ["sc2-code-open", "sc2-train-rma-dp4"])
def test_no_tpu_fails_without_result(cell):
    p = _run(common.ROOT, cell)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU found" in p.stderr


def test_benchmark_files_alone_fail_without_result(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
