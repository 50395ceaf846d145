"""Least time of each prefill in the traced slice (operations over peak or
bytes over bandwidth, whichever is larger) over the device time of the
``prefill_into_slot`` program runs there."""
from bench.lib import flops, readers


def read(trace, record):
    cfg, peak = record["cfg"], record["peak"]
    least = sum(flops.least_time(flops.prefill_flops(cfg, n), flops.prefill_bytes(cfg, n), peak)
                for _, _, pre, _ in readers.traced_steps(record) for n in pre)
    return readers.share(least, sum(trace.programs(min(trace.devices), "prefill_into_slot")))
