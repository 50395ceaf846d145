"""Median over the requests admitted in the traced slice of how long each
waited in the scheduler: the start of its ``serve.prefill`` span minus
the start of its ``serve.submit`` span, for the requests whose submission
is in the trace."""
import numpy as np

from bench.lib import program_trace


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    waits = prog.admit_waits(trace)
    return float(np.median(waits)) if waits else None
