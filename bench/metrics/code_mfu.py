"""Model operations of every prefill and decoded token in the traced slice
over the slice's seconds times the chip's peak."""
from bench.lib import readers


def read(trace, record):
    work = readers.step_flops(record["cfg"], readers.traced_steps(record))
    return readers.share(work, trace.window_s * record["peak"]["bf16_flops"])
