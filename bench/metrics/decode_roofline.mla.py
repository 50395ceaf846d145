"""Least time of a decode step over its device time, in the traced slice:
the weights once (of the routed experts only the ``experts_hit`` held ones
the step's rows chose) and the ``live_tokens`` latents, at the chip's
bandwidth (or its operations at the peak rate, where larger), from the
engine's ``serve.decode`` spans, over the device time of the
``decode_step`` program runs; the means of the two."""
import numpy as np

from bench.lib import flops_mla, program_trace


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    cfg, peak = record["cfg"], record["peak"]
    least = [flops_mla.least_time(
        flops_mla.decode_flops(cfg, a["rows"], a["live_tokens"], a["moe_held"]),
        flops_mla.decode_bytes(cfg, a["live_tokens"], a["experts_hit"]), peak)
        for _, _, _, a in prog.inside("serve.decode", trace.t0, trace.t1)
        if "experts_hit" in a]
    runs = trace.programs(min(trace.devices), "decode_step")
    if not least or not runs:
        return None
    return 100.0 * float(np.mean(least)) / float(np.mean(runs))
