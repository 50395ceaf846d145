"""Milliseconds a train step in which some ``collective-permute`` ran,
averaged over the devices."""
import numpy as np


def read(trace, record):
    steps = len(trace.programs(min(trace.devices), record["step_program"]))
    if not steps:
        return None
    return 1e3 * float(np.mean([trace.collective(d)[0] for d in trace.devices])) / steps
