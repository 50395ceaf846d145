"""Mean over the ``serve.step`` spans wholly inside the traced slice of
the host's own time in a tick: the span's length less the time it waits
for device results (its ``serve.*.sync`` spans)."""
import numpy as np

from bench.lib import program_trace


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    own = [(step[2] - step[1]) - sum(s[2] - s[1] for s in prog.syncs_in(step))
           for step in prog.steps(trace)]
    return 1e3 * float(np.mean(own)) if own else None
