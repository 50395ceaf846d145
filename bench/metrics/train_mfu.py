"""Forward and backward operations of the train steps in the traced slice
(recomputation not counted) over the slice's seconds times the chips'
peak."""
from bench.lib import flops, readers


def read(trace, record):
    steps = len(trace.programs(min(trace.devices), record["step_program"]))
    work = steps * flops.train_flops(record["cfg"], record["global_batch"], record["seq_len"])
    return readers.share(work, trace.window_s * len(trace.devices) * record["peak"]["bf16_flops"])
