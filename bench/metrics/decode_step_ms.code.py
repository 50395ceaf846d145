"""Mean device time of a ``decode_step`` program run in the traced slice."""
import numpy as np


def read(trace, record):
    runs = trace.programs(min(trace.devices), "decode_step")
    return 1e3 * float(np.mean(runs)) if runs else None
