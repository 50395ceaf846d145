"""Operation and byte counts against hand counts at starcoder2-3b shapes."""
import pytest

from bench.lib import common, flops


@pytest.fixture(scope="module")
def cfg():
    return common.config("starcoder2-3b-serve")


def test_weights_by_hand(cfg):
    # q 3072x24x128, k and v 3072x2x128, o 24x128x3072, MLP 2x3072x12288
    per_layer = 3072 * 3072 + 2 * 3072 * 256 + 3072 * 3072 + 2 * 3072 * 12288
    assert per_layer == 95_944_704
    assert flops.matmul_params(cfg) == 30 * per_layer + 3072 * 49152
    assert flops.weight_bytes(cfg) == 2 * (30 * 95_944_704 + 150_994_944)


def test_kv_bytes_by_hand(cfg):
    # 30 layers x (K and V) x 2 kv heads x 128 x 2 bytes = 30 KiB a token
    assert flops.kv_bytes_per_token(cfg) == 30 * 2 * 2 * 128 * 2 == 30 * 1024


def test_prefill_by_hand(cfg):
    s = 1024
    dense = 2 * s * 30 * 95_944_704
    head = 2 * 3072 * 49152               # the last position only
    attn = 4 * 30 * 24 * 128 * s * (s + 1) / 2
    assert flops.prefill_flops(cfg, s) == pytest.approx(dense + head + attn, rel=1e-12)
    assert flops.prefill_bytes(cfg, s) == flops.weight_bytes(cfg) + s * 30 * 1024


def test_decode_by_hand(cfg):
    ctx = [100, 2000]
    per_token = 2 * (30 * 95_944_704 + 150_994_944)
    attn = sum(4 * 30 * 24 * 128 * (c + 1) for c in ctx)
    assert flops.decode_flops(cfg, ctx) == pytest.approx(2 * per_token + attn, rel=1e-12)
    kv = 30 * 1024
    assert flops.decode_bytes(cfg, ctx) == flops.weight_bytes(cfg) + 2100 * kv + 2 * kv


def test_train_by_hand():
    cfg = common.config("starcoder2-3b-train-dp4")
    b, s = 8, 1024
    fwd = 2 * b * s * (2 * 95_944_704 + 150_994_944) + b * 4 * 2 * 24 * 128 * s * (s + 1) / 2
    assert flops.train_flops(cfg, b, s) == pytest.approx(3 * fwd, rel=1e-12)


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 10.0, peak) == 10.0     # compute bound
    assert flops.least_time(10.0, 1000.0, peak) == 100.0    # memory bound
