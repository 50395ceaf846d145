"""Pages holding tokens over pages reserved in the KV pool, each summed
over the ticks of the traced slice as each tick starts (the arguments of
the engine's ``serve.step`` spans)."""
from bench.lib import program_trace, readers


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    steps = prog.steps(trace)
    return readers.share(sum(s[3]["pages_used"] for s in steps),
                         sum(s[3]["pages_reserved"] for s in steps))
