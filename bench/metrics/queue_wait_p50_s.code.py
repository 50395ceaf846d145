"""Median over requests of the start of the step that first shows a
request, minus when it was due (host clock)."""
import numpy as np


def read(trace, record):
    waits = record.get("queue_waits")
    return float(np.median(waits)) if waits else None
