"""The reduction from a trace to busy time, per-program time and exposed
collective time: on intervals worked out by hand, and on a small trace
recorded on a TPU v5e (``fixtures/``, made by ``record_fixture.py``)."""
from pathlib import Path

import numpy as np
import pytest

from bench.lib import trace as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _trace(ops, modules=(), spans=(), t0=0.0, t1=10.0):
    return tr.Trace({0: tr.Device(list(ops), list(modules))},
                    list(spans), t0, t1)


def test_union_and_subtraction_by_hand():
    iv = np.asarray([[0, 2], [1, 3], [5, 6], [5.5, 5.7]], float)
    np.testing.assert_array_equal(tr.merge(iv), [[0, 3], [5, 6]])
    assert tr.length(tr.merge(iv)) == 4.0
    a = np.asarray([[0, 10]], float)
    b = np.asarray([[1, 2], [4, 5], [9, 12]], float)
    np.testing.assert_array_equal(tr.subtract(a, b), [[0, 1], [2, 4], [5, 9]])
    np.testing.assert_array_equal(tr.clip(b, 1.5, 10), [[1.5, 2], [4, 5], [9, 10]])


def test_busy_programs_and_exposed_collective_by_hand():
    ops = [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
           ("collective-permute-done.3", 3.5, 6.0), ("fusion.4", 5.0, 5.5),
           ("copy.5", 9.0, 11.0)]
    t = _trace(ops, modules=[("jit_step(1)", 1.0, 6.0), ("jit_step(1)", 9.0, 11.0)],
               spans=[("bench.window", 0, 10), ("bench.step", 0.5, 6.5),
                      ("bench.wait_due", 6.5, 9.0)])
    # busy: [1, 6] and [9, 10] (clipped at the slice's end) = 6 of 10 s
    assert t.busy_s(0) == pytest.approx(6.0)
    assert t.idle_share() == pytest.approx(0.4)
    # one program run wholly inside the slice
    assert t.programs(0, "jit_step") == [5.0]
    # collective 3.5-6.0; other ops cover 3.5-4.0 and 5.0-5.5: exposed 1.5
    assert t.collective(0) == (pytest.approx(2.5), pytest.approx(1.5))
    # by name without the instance number; copy.5 runs past the slice
    assert t.top_ops(0) == [["fusion", 4.5], ["collective-permute-done", 2.5]]
    # gaps [6, 9] and [0, 1], each named by the host span covering most of it
    assert t.idle_gaps(0)[0] == ["bench.wait_due", pytest.approx(3.0)]
    assert t.idle_gaps(0)[1] == ["bench.step", pytest.approx(1.0)]


@pytest.fixture(scope="module")
def one_chip():
    return tr.load(FIXTURES / "v5e_1chip.xplane.pb")


def test_fixture_one_chip_by_hand(one_chip):
    """Three runs of a four-matmul program; the slice holds the last two
    (the device clock reads about 1.1 ms behind the host spans here, so the
    first run falls before ``bench.window`` opens)."""
    t = one_chip
    assert t.window_s == pytest.approx(10.71225e-3, abs=1e-9)
    # runs 2 and 3: 13 + 3 + 45943 + 44992 + 45178 + 45966 ns and
    # 14 + 3 + 45940 + 44992 + 45178 + 45955 ns of operations
    assert t.busy_s(0) == pytest.approx((182095 + 182082) * 1e-9, abs=2e-9)
    assert t.idle_share() == pytest.approx(1 - 364177e-9 / 10.71225e-3, abs=1e-6)
    assert t.programs(0, "jit_body") == pytest.approx([182.110e-6, 182.096e-6], abs=2e-9)
    assert t.collective(0) == (0.0, 0.0)
    assert t.top_ops(0)[0][0] == "convolution_tanh_fusion"
    # the longest gap: the end of run 3 to the end of the slice
    assert t.idle_gaps(0)[0][1] == pytest.approx(4.191767e-3, abs=2e-9)


def test_fixture_busy_against_a_sampled_timeline(one_chip):
    """Busy time again, from a timeline sampled every nanosecond."""
    t = one_chip
    lo = int(round(t.t0 * 1e9))
    line = np.zeros(int(round(t.t1 * 1e9)) - lo, bool)
    for _, s, e in t.devices[0].ops:
        a, b = int(round(s * 1e9)) - lo, int(round(e * 1e9)) - lo
        line[max(a, 0):max(min(b, len(line)), 0)] = True
    assert line.sum() * 1e-9 == pytest.approx(t.busy_s(0), abs=5e-9)


def test_fixture_four_chips_by_hand():
    """Each chip: four matmuls, then a ring shift (``collective-permute``)
    that nothing overlaps; the slice holds the last two of three runs."""
    t = tr.load(FIXTURES / "v5e_4chip.xplane.pb")
    assert sorted(t.devices) == [0, 1, 2, 3]
    # chip 0, runs 2 and 3: 2 + 2 + 46212 + 44992 + 45177 + 44992 + 1642 +
    # 91458 + 6329 ns and 2 + 2 + 45933 + 44992 + 45178 + 44992 + 1920 +
    # 91728 + 6261 ns of operations
    assert t.busy_s(0) == pytest.approx((280806 + 281008) * 1e-9, abs=3e-9)
    assert t.programs(0, "jit_body") == pytest.approx([281.688e-6, 331.653e-6], abs=2e-9)
    # the permutes in flight from start to done: 93102 and 93650 ns, with
    # no other operation running meanwhile, so all of it is exposed
    inflight, exposed = t.collective(0)
    assert inflight == pytest.approx((93102 + 93650) * 1e-9, abs=2e-9)
    assert exposed == pytest.approx(inflight, abs=2e-9)
    for d in t.devices:
        got = t.collective(d)
        assert 150e-6 < got[0] < 250e-6 and got[1] == pytest.approx(got[0], rel=1e-6)
