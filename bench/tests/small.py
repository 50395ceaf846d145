"""Small stand-ins for the cells' configurations and traffic, for runs of
the harness on the CPU."""
from bench.lib import common

WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)


def config(name: str) -> dict:
    cfg = common.config(name)
    cfg.update(WIDTHS)
    if "serving" in cfg:
        cfg["serving"] = dict(cfg["serving"], n_slots=4, max_seq=256)
    return cfg


def workload(name: str) -> dict:
    work = common.workload(name)
    if work["driver"] == "train_step":
        work.update(seq_len=32)
        return work
    work["prompt"].update(median=60, min=16, max=128, buckets=[32, 64, 128])
    work["output"].update(median=8, min=2, max=16)
    work.update(check_tokens=128, rate_per_s=20)
    return work


def run(cell: str, seed: int = 2**31 + 3, seconds: float = 2.0, **widths) -> dict:
    """One run of ``cell`` at the small size, ``widths`` overriding it."""
    from bench import run as bench_run

    cfg = config(common.workload(cell)["config"])
    cfg.update(widths)
    return bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                           str(seconds)], need_chip=False, cfg=cfg, work=workload(cell))
