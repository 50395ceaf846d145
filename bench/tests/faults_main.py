"""Runs the small train cell on four CPU devices, unbroken and with each
fault planted in the program's train step; prints one JSON line a run.
``test_bench_faults.py`` starts it in a process of its own."""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import calibrate  # noqa: E402
from bench.lib import program  # noqa: E402
from bench.tests import small  # noqa: E402

MAKE = program.train_step

for fault in [None] + sys.argv[1:]:
    program.train_step = MAKE
    if fault:
        calibrate.plant(fault)
    out = small.run("sc2-train-rma-dp4", seconds=0.5)
    print("FAULT " + json.dumps({"fault": fault, "correct": out["correct"],
                                 "checks": out["checks"]}), flush=True)
