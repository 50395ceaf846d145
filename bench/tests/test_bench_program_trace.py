"""The program's spans in a traced slice (``bench/lib/program_trace.py``)
and the four readers of them: on a trace built by hand with a known skew
between the host and device clocks, on a trace of a small serving engine
recorded here on the CPU, and on the committed chip fixtures, where the
existing readers still read what they read before."""
import glob
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench.lib import common, e2e, program_trace
from bench.lib import trace as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"
NEW = ["admit_wait_p50_s.code", "first_token_held_p50_ms.code",
       "kv_pages_used_share.code", "step_host_ms.code"]
DELTA = 1.1e-3          # host clock minus device clock, injected
TICK = 0.1


def _read(metric, trace, record=None):
    return common.metric_reader(metric).read(trace, record or {})


def _tick(b, k, argmax=True):
    """Host spans of tick ``k`` starting at ``b``, and its device runs on
    the host clock: a prefill (ticks 0 and 2) of a request submitted
    ``k + 1`` ms before, then a decode and the argmax program that reads
    its tokens, then the commit loop and a slot release (an eager op)
    inside it."""
    spans, ops, mods = [], [], []
    used, reserved = 10 * (k + 1), 40 * (k + 1)
    spans.append(("serve.step", b, b + 0.099, {"tick": k, "live": k, "queued": 0,
                                               "pages_reserved": reserved,
                                               "pages_used": used}))
    spans.append(("serve.admit", b + 0.001, b + 0.030, {}))
    if k % 2 == 0:
        sub = b + 0.002 - 0.001 * (k + 1)
        spans.append(("serve.submit", sub, sub + 1e-6, {"rid": k}))
        spans.append(("serve.prefill", b + 0.002, b + 0.025,
                      {"rid": k, "prompt_len": 512, "slot": 0}))
        spans.append(("serve.prefill.sync", b + 0.004, b + 0.024, {}))
        mods.append(("jit_prefill_into_slot(7)", b + 0.0025, b + 0.0235))
        ops.append(("fusion.1", b + 0.0025, b + 0.0235))
    lat_in, lat_out = (0.0005, 0.0002, 0.0003)[k], (0.0005, 0.0001, 0.0004)[k]
    spans.append(("serve.decode", b + 0.032, b + 0.090, {}))
    spans.append(("serve.decode.sync", b + 0.034, b + 0.089, {}))
    mods.append(("jit_decode_step(9)", b + 0.032 + lat_in, b + 0.088))
    ops.append(("while.2", b + 0.032 + lat_in, b + 0.088))
    if argmax:
        mods.append(("jit__getitem(3)", b + 0.0881, b + 0.0882))
        ops.append(("slice.1", b + 0.0881, b + 0.0882))
        mods.append(("jit_argmax(4)", b + 0.0883, b + 0.089 - lat_out))
        ops.append(("reduce.5", b + 0.0883, b + 0.089 - lat_out))
    spans.append(("serve.commit", b + 0.0905, b + 0.0985, {}))
    ops.append(("convert.4", b + 0.0906, b + 0.091))
    spans.append(("serve.release", b + 0.097, b + 0.0983, {"slot": 0, "pages": 4}))
    ops.append(("copy.3", b + 0.0975, b + 0.098))
    return spans, ops, mods


def _synthetic(argmax=True):
    """Three ticks in a 0.3 s slice; the device's times read ``DELTA``
    behind the host's."""
    spans, ops, mods = [], [], []
    for k in range(3):
        s, o, m = _tick(0.001 + k * TICK, k, argmax)
        spans += s
        ops += o
        mods += m
    dev = tr.Device([(n, a - DELTA, e - DELTA) for n, a, e in ops],
                    [(n, a - DELTA, e - DELTA) for n, a, e in mods])
    bench = [("bench.window", 0.0, 0.302)] + [
        ("bench.step", 0.0005 + k * TICK, 0.1 + k * TICK) for k in range(3)]
    trace = tr.Trace({0: dev}, bench, 0.0, 0.302)
    prog = program_trace.Program(sorted(spans, key=lambda s: s[1]))
    prog.bounds, prog.runs = program_trace.clock_bounds(spans, dev.modules, 0.0, 0.302)
    return trace, prog


def test_clock_offset_from_decode_runs_with_a_known_skew():
    _, prog = _synthetic()
    # dispatch latencies 0.5, 0.2, 0.3 ms and read latencies 0.5, 0.1, 0.4
    # ms: δ in [DELTA - 0.2 ms, DELTA + 0.1 ms]
    assert prog.runs == 3
    assert prog.bounds == pytest.approx((DELTA - 0.0002, DELTA + 0.0001), abs=1e-12)
    assert prog.clock_offset_s == pytest.approx(DELTA - 0.00005, abs=1e-12)
    assert prog.bounds[0] <= DELTA <= prog.bounds[1]


def test_without_the_argmax_program_the_decode_run_bounds_the_read():
    _, prog = _synthetic(argmax=False)
    # the sync ends 1 ms after the decode run, whatever the argmax took
    assert prog.bounds == pytest.approx((DELTA - 0.0002, DELTA + 0.001), abs=1e-12)


def test_no_decode_runs_give_no_offset():
    trace, prog = _synthetic()
    assert program_trace.clock_bounds(prog.spans, [], 0.0, 0.302) == (None, 0)
    assert program_trace.Program(prog.spans).clock_offset_s is None


def test_idle_gaps_named_by_program_spans_on_the_device_clock():
    trace, prog = _synthetic()
    # the commit loop's gaps lie wholly inside serve.commit only once the
    # host spans move onto the device clock; before, the harness's step
    # covers more of them
    named = program_trace.idle_gaps(trace, 0, prog)
    plain = trace.idle_gaps(0)
    assert [g[1] for g in named] == [g[1] for g in plain]
    assert {g[0] for g in plain} <= {"bench.step", "none"}
    commit = [g for g in named if g[0] == "serve.commit"]
    assert len(commit) == 3 and all(g[1] == pytest.approx(0.007 - 0.0005) for g in commit)
    # one gap at a tick's edge is the harness's at the lower bound and the
    # engine's at the upper one
    assert {g[0] for g in named} == {"serve.commit", "serve.step", "bench.step",
                                     "bench.step|serve.step"}


def test_a_gap_the_two_bounds_name_differently_is_not_attributed():
    """Spans A then B on the host clock; the device is idle over [0.4,
    1.5] s of its own clock.  At δ = -0.2 s A covers most of the gap, at
    δ = 0.4 s B does; at a single δ the gap has one name."""
    dev = tr.Device([("op", 0.0, 0.4), ("op", 1.5, 2.0)], [])
    trace = tr.Trace({0: dev}, [("bench.window", 0.0, 2.0)], 0.0, 2.0)
    spans = [("serve.admit", 0.0, 1.0, {}), ("serve.release", 1.0, 2.0, {})]
    both = program_trace.Program(spans, bounds=(-0.2, 0.4))
    assert program_trace.idle_gaps(trace, 0, both) == [
        ["serve.admit|serve.release", pytest.approx(1.1)]]
    for d, name in ((-0.2, "serve.admit"), (0.4, "serve.release")):
        one = program_trace.Program(spans, bounds=(d, d))
        assert program_trace.idle_gaps(trace, 0, one) == [[name, pytest.approx(1.1)]]


def test_new_readers_with_known_values():
    trace, prog = _synthetic()
    program_trace.remember(trace, prog)
    # prefills of ticks 0 and 2 waited 1 and 3 ms
    assert _read("admit_wait_p50_s.code", trace) == pytest.approx(0.002)
    # each first token is held from its sync's end to its step's end
    assert _read("first_token_held_p50_ms.code", trace) == pytest.approx(99 - 24)
    # pages: 10 + 20 + 30 holding tokens of 40 + 80 + 120 reserved
    assert _read("kv_pages_used_share.code", trace) == pytest.approx(25.0)
    # a tick's 99 ms less 55 ms of decode sync, and 20 ms more on a prefill
    assert _read("step_host_ms.code", trace) == pytest.approx((24 + 44 + 24) / 3)


def test_new_readers_read_nothing_without_program_spans():
    trace, _ = _synthetic()
    program_trace.remember(trace, program_trace.Program([]))
    assert all(_read(m, trace) is None for m in NEW)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """An empty trace directory for the harness, and no slice read yet."""
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(program_trace, "_SEEN", {})
    return tmp_path


def _place(path, root, cell="cell"):
    dst = root / cell / "plugins" / "profile" / "1" / "t.xplane.pb"
    dst.parent.mkdir(parents=True)
    shutil.copy(path, dst)
    return dst


@pytest.mark.parametrize("fixture", ["v5e_1chip", "v5e_4chip"])
def test_new_readers_on_a_trace_of_the_parent(fixture, trace_dir, capsys):
    """A program without ``serve.*`` spans: the file is found, and every
    new reader reads nothing and raises nothing."""
    _place(FIXTURES / f"{fixture}.xplane.pb", trace_dir)
    t = tr.load(FIXTURES / f"{fixture}.xplane.pb")
    assert all(_read(m, t) is None for m in NEW)
    assert program_trace.of(t).spans == []
    assert "0 serve.step and 3 bench.step spans" in capsys.readouterr().out


def test_a_file_of_another_slice_is_not_read(trace_dir):
    _place(FIXTURES / "v5e_4chip.xplane.pb", trace_dir)
    t = tr.load(FIXTURES / "v5e_1chip.xplane.pb")
    assert program_trace.of(t) is None
    assert all(_read(m, t) is None for m in NEW)


def _records(t):
    """Records for the existing readers, with a few small steps in the
    slice."""
    mid = 0.5 * (t.t0 + t.t1)
    peak = e2e.peak("TPU v5 lite")
    code = {"cfg": common.config("starcoder2-3b-serve"), "peak": peak,
            "traced": (t.t0, t.t1), "queue_waits": [0.25, 0.5, 0.125],
            "steps": [(t.t0 + 1e-4, mid, [16], [20, 30]), (mid, t.t1 - 1e-4, [], [21, 31])]}
    train = {"cfg": common.config("starcoder2-3b-train-dp4"), "peak": peak,
             "traced": (t.t0, t.t1), "step_program": "jit_body",
             "global_batch": 1, "seq_len": 16}
    return code, train


# what the existing readers read on the fixtures with the records above
BEFORE = {
    "v5e_1chip": {"queue_wait_p50_s.code": 0.25, "prefill_roofline.code": None,
                  "code_mfu": 5.531539221487615,
                  "device_idle_share.code": 96.60036873672671,
                  "decode_step_ms.code": None, "train_mfu": 3.1205722467979577,
                  "grad_sync_ms": 0.0, "sync_exposed_ms": 0.0,
                  "device_idle_share.train": 96.60036873672671},
    "v5e_4chip": {"queue_wait_p50_s.code": 0.25, "prefill_roofline.code": None,
                  "code_mfu": 4.82836502650768,
                  "device_idle_share.code": 95.43767118811616,
                  "decode_step_ms.code": None, "train_mfu": 0.6809705805122025,
                  "grad_sync_ms": 0.0923373750000081,
                  "sync_exposed_ms": 0.0923373750000081,
                  "device_idle_share.train": 95.43767118811616},
}


@pytest.mark.parametrize("fixture,metric", [(f, m) for f in BEFORE for m in BEFORE[f]])
def test_existing_readers_read_the_fixtures_as_before(fixture, metric):
    t = tr.load(FIXTURES / f"{fixture}.xplane.pb")
    code, train = _records(t)
    record = code if metric.endswith(".code") or metric == "code_mfu" else train
    got = _read(metric, t, record)
    want = BEFORE[fixture][metric]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


def test_readers_agree_with_the_engine_on_a_cpu_trace(trace_dir):
    """A small paged engine traced on the CPU: what the readers take from
    its spans is what its own counters and completions say."""
    common.use_program()
    import jax

    from repro.configs.tiny import tiny_config
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = tiny_config("qwen3-4b")
    m = build_model(cfg)
    eng = ServeEngine(m, m.init(jax.random.PRNGKey(0)), n_slots=2, max_seq=32,
                      paged_kv=True, page_tokens=8)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=5 + i % 3 * 4),
                    max_new_tokens=2 + i % 4) for i in range(7)]
    for r in reqs[:3]:
        eng.submit(r)
    eng.step()          # compiles outside the trace; admits rids 0 and 1
    st0 = eng.stats()
    run_dir = trace_dir / "cell"
    jax.profiler.start_trace(str(run_dir))
    try:
        with common.span("bench.window"):
            for r in reqs[3:]:
                eng.submit(r)
            while eng.scheduler.pending_count or eng.slot_req:
                eng.step()
    finally:
        jax.profiler.stop_trace()
    st1 = eng.stats()
    (path,) = glob.glob(str(run_dir / "plugins/profile/*/*.xplane.pb"))
    t = tr.load(path)
    prog = program_trace.of(t)
    assert len(prog.steps(t)) == st1["ticks"] - st0["ticks"]
    share = 100 * ((st1["tick_pages_used"] - st0["tick_pages_used"])
                   / (st1["tick_pages_reserved"] - st0["tick_pages_reserved"]))
    assert _read("kv_pages_used_share.code", t) == pytest.approx(share, rel=1e-12)
    admitted = [c for c in eng.done if c.rid >= 2]
    assert len(admitted) == 5
    # rid 2 was submitted before the trace: its wait is not read
    waits = [c.t_admit - c.t_submit for c in admitted if c.rid >= 3]
    assert sorted(prog.admit_waits(t)) == pytest.approx(sorted(waits), abs=2e-5)
    assert _read("admit_wait_p50_s.code", t) == pytest.approx(np.median(waits), abs=2e-5)
    held = [c.t_out - c.t_first for c in admitted]
    assert _read("first_token_held_p50_ms.code", t) == pytest.approx(
        1e3 * np.median(held), abs=0.5)
    assert 0 < _read("step_host_ms.code", t)
