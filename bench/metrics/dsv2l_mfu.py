"""Model operations of every prefill and decode step in the traced slice,
from the engine's counters on its ``serve.prefill`` and ``serve.decode``
spans, over the slice's seconds times the chip's peak: the share of the
whole step's peak the served work used."""
from bench.lib import flops_mla, program_trace, readers


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    cfg = record["cfg"]
    work = sum(flops_mla.prefill_flops(cfg, a["prompt_len"], a["moe_held"])
               for _, _, _, a in prog.inside("serve.prefill", trace.t0, trace.t1)
               if "moe_held" in a)
    work += sum(flops_mla.decode_flops(cfg, a["rows"], a["live_tokens"], a["moe_held"])
                for _, _, _, a in prog.inside("serve.decode", trace.t0, trace.t1)
                if "moe_held" in a)
    return readers.share(work, trace.window_s * record["peak"]["bf16_flops"])
