"""Held experts a decode step hit over the held experts of every expert
layer, summed over the ``serve.decode`` spans of the traced slice: how
much of the held expert weight a decode step must read."""
from bench.lib import program_trace, readers


def read(trace, record):
    prog = program_trace.of(trace)
    if prog is None:
        return None
    cfg = record["cfg"]
    per_step = cfg["n_routed_experts"] * (cfg["num_hidden_layers"]
                                          - cfg["first_k_dense_replace"])
    spans = [a for _, _, _, a in prog.inside("serve.decode", trace.t0, trace.t1)
             if "experts_hit" in a]
    return readers.share(sum(a["experts_hit"] for a in spans), per_step * len(spans))
