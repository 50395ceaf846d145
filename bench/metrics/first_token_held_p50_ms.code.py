"""Median over the prefills in the traced slice of how long the engine
holds a first token: the end of the ``serve.step`` that made it minus the
end of the ``serve.prefill.sync`` that read it on the host."""
import numpy as np

from bench.lib import program_trace


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    held = [step[2] - s[2] for step in prog.steps(trace)
            for s in prog.syncs_in(step) if s[0] == "serve.prefill.sync"]
    return 1e3 * float(np.median(held)) if held else None
