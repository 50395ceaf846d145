"""Least time of each prefill in the traced slice -- its operations (causal
attention in the cheaper MLA form, routed operations from the engine's
``moe_held``) over the peak rate or its bytes (weights with the
``experts_hit`` held experts, latents written) over the bandwidth,
whichever is larger, from the ``serve.prefill`` spans -- over the device
time of the ``prefill_into_slot`` program runs there."""
from bench.lib import flops_mla, program_trace, readers


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    cfg, peak = record["cfg"], record["peak"]
    least = sum(flops_mla.least_time(
        flops_mla.prefill_flops(cfg, a["prompt_len"], a["moe_held"]),
        flops_mla.prefill_bytes(cfg, a["prompt_len"], a["experts_hit"]), peak)
        for _, _, _, a in prog.inside("serve.prefill", trace.t0, trace.t1)
        if "experts_hit" in a)
    return readers.share(least, sum(trace.programs(min(trace.devices),
                                                   "prefill_into_slot")))
