"""The DeepSeek-V2-Lite cell's own files: the plain reference
(``reference_mla``) against hand-written arithmetic and against the
program's float32 forward, the operation and byte counts (``flops_mla``)
by hand, the cell's four readers on a trace built by hand and on a trace
of the parent, and the cell's driver run end to end on the CPU."""
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import common, e2e, flops_mla, program, program_mla, program_trace
from bench.lib import reference_mla, weights
from bench.lib import trace as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"
READERS = ["decode_roofline.mla", "prefill_roofline.mla", "dsv2l_mfu",
           "experts_hit_share.mla"]
SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=3, vocab_size=512,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, moe_intermediate_size=32)


def small_config(**kw):
    cfg = common.config("deepseek-v2-lite-serve")
    cfg.update(SMALL, **kw)
    cfg["serving"] = dict(cfg["serving"], n_slots=4, max_seq=256)
    return cfg


def _read(metric, trace, record):
    return common.metric_reader(metric).read(trace, record)


# -- the reference ------------------------------------------------------------

def test_attention_by_hand():
    """One MLA layer of 3 tokens and 2 heads written out with loops, in
    float64, against the reference's blockwise expanded form."""
    cfg = small_config()
    cfg.update(qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=3,
               kv_lora_rank=5, hidden_size=6)
    rng = np.random.default_rng(0)
    d, h, r = 6, 2, 5
    a = {"w_q": rng.normal(size=(d, h, 8)), "w_dkv": rng.normal(size=(d, r)),
         "kv_norm": {"scale": 1 + 0.1 * rng.normal(size=r)},
         "w_kr": rng.normal(size=(d, 4)), "w_uk": rng.normal(size=(r, h, 4)),
         "w_uv": rng.normal(size=(r, h, 3)), "wo": rng.normal(size=(h, 3, d))}
    x = rng.normal(size=(1, 3, d))
    inv = reference_mla.yarn_inv_freq(cfg)
    m = 1.0                  # mscale(40, 0.707) / mscale(40, 0.707)

    def rope(v, t):          # half-split rotation of one vector at position t
        c, s = m * np.cos(t * inv), m * np.sin(t * inv)
        return np.concatenate([v[:2] * c - v[2:] * s, v[2:] * c + v[:2] * s])

    def rms(v, g):
        return v / np.sqrt(np.mean(v * v) + cfg["rms_norm_eps"]) * g

    scale = 8 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    want = np.zeros((3, d))
    for t in range(3):
        for i in range(h):
            q = x[0, t] @ a["w_q"][:, i]
            q = np.concatenate([q[:4], rope(q[4:], t)])
            ks, vs = [], []
            for u in range(t + 1):
                c = rms(x[0, u] @ a["w_dkv"], a["kv_norm"]["scale"])
                ks.append(np.concatenate([c @ a["w_uk"][:, i], rope(x[0, u] @ a["w_kr"], u)]))
                vs.append(c @ a["w_uv"][:, i])
            sc = np.array([q @ k for k in ks]) * scale
            w = np.exp(sc - sc.max())
            want[t] += (w / w.sum()) @ np.array(vs) @ a["wo"][i]
    f32 = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), a)
    got = reference_mla._attention(jnp.asarray(x, jnp.float32), f32, cfg, False)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-4, atol=1e-4)


def test_experts_by_hand():
    """The held experts' gate-weighted SwiGLU plus the shared one, for two
    tokens, written out against the reference."""
    cfg = small_config(hidden_size=4, moe_intermediate_size=3, num_experts_per_tok=2)
    cfg["experts_held"] = [2, 4]
    rng = np.random.default_rng(1)
    m = {"router": rng.normal(size=(4, 6)), "wi": rng.normal(size=(2, 4, 6)),
         "wo": rng.normal(size=(2, 3, 4)),
         "shared": {"wi": rng.normal(size=(4, 6)), "wo": rng.normal(size=(3, 4))}}
    h = rng.normal(size=(1, 2, 4))

    def swiglu(v, wi, wo):
        g, u = (v @ wi)[:3], (v @ wi)[3:]
        return (g / (1 + np.exp(-g)) * u) @ wo

    want = np.zeros((2, 4))
    for t in range(2):
        p = np.exp(h[0, t] @ m["router"])
        p /= p.sum()
        top = np.argsort(-p)[:2]
        want[t] = swiglu(h[0, t], m["shared"]["wi"], m["shared"]["wo"])
        for e in top:
            if 2 <= e < 4:
                want[t] += p[e] * swiglu(h[0, t], m["wi"][e - 2], m["wo"][e - 2])
    f32 = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), m)
    got = reference_mla._experts(jnp.asarray(h, jnp.float32), f32, cfg, False)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5, atol=1e-5)


def test_forward_matches_the_program():
    """The reference reads the program's weight layout right: its logits
    are the program's float32 forward's (the held path, blockwise MLA)."""
    cfg = small_config()
    mc = program_mla.model_config(cfg, {"dtype": "float32", "param_dtype": "float32"})
    model = program.build_model(mc)
    w = weights.make(program.param_shapes(model), 3, 0.2, "float32")
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 512)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(model.forward)(w, {"tokens": tokens})
    got = jax.jit(lambda w, t: reference_mla.logits(w, reference_mla.hidden(w, t, cfg), cfg))(
        w, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[..., :512],
                               rtol=2e-4, atol=2e-4)


def test_unsupported_settings_are_refused():
    for key, value in (("scoring_func", "sigmoid"), ("topk_method", "group_limited_greedy"),
                       ("routed_scaling_factor", 16)):
        with pytest.raises(common.BenchError):
            program_mla.model_config(small_config(**{key: value}),
                                     {"dtype": "float32", "param_dtype": "float32"})


# -- operations and bytes -----------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return common.config("deepseek-v2-lite-serve")


def test_weights_by_hand(cfg):
    # q 2048x16x192, latent 2048x512, rope key 2048x64, W_uk and W_uv
    # 512x16x128 each, o 16x128x2048
    attn = 2048 * 3072 + 2048 * 512 + 2048 * 64 + 2 * 512 * 2048 + 2048 * 2048
    assert attn == 13_762_560
    dense, shared, router = 3 * 2048 * 10944, 3 * 2048 * 2816, 2048 * 64
    assert flops_mla.token_params(cfg) == 27 * attn + dense + 26 * (shared + router)
    expert = 3 * 2048 * 1408
    assert flops_mla.weight_bytes(cfg, 5) == 2 * (
        flops_mla.token_params(cfg) + 2048 * 102400 + 5 * expert)
    # the held weights, all experts read: ~3.11 B parameters with the embedding
    held = flops_mla.token_params(cfg) + 2 * 2048 * 102400 + 26 * 8 * expert
    assert held == pytest.approx(3.11e9, rel=0.01)


def test_latent_bytes_by_hand(cfg):
    assert flops_mla.latent_bytes_per_token(cfg) == 27 * (512 + 64) * 2 == 31104


def test_prefill_and_decode_by_hand(cfg):
    s, held, hit = 1024, 700, 40
    pairs = s * (s + 1) / 2
    expand = 2 * 512 * 16 * 256
    attn = 27 * (10240 * pairs + expand * s)          # expanded: cheaper here
    assert 27 * (34816 * pairs + expand * s) > attn
    want = (2 * s * flops_mla.token_params(cfg) + 2 * held * 3 * 2048 * 1408
            + 2 * 2048 * 102400 + attn)
    assert flops_mla.prefill_flops(cfg, s, held) == pytest.approx(want, rel=1e-12)
    assert flops_mla.prefill_bytes(cfg, s, hit) == (flops_mla.weight_bytes(cfg, hit)
                                                     + s * 31104)
    rows, live = 16, 40000
    attn = 27 * (34816 * live + expand * rows)         # absorbed: cheaper here
    want = (2 * rows * (flops_mla.token_params(cfg) + 2048 * 102400)
            + 2 * held * 3 * 2048 * 1408 + attn)
    assert flops_mla.decode_flops(cfg, rows, live, held) == pytest.approx(want, rel=1e-12)
    assert flops_mla.decode_bytes(cfg, live, hit) == (flops_mla.weight_bytes(cfg, hit)
                                                       + live * 31104)


# -- the readers --------------------------------------------------------------

def _synthetic():
    """Two ticks in a 0.2 s slice, host and device on one clock: a prefill
    of 1024 tokens in the first, a decode step in each."""
    spans, mods = [], []
    for k in range(2):
        b = 0.001 + 0.1 * k
        if k == 0:
            spans.append(("serve.prefill", b, b + 0.05, {"rid": 0, "prompt_len": 1024,
                                                         "slot": 0, "moe_held": 1500,
                                                         "experts_hit": 200}))
            mods.append(("jit_prefill_into_slot(7)", b + 0.001, b + 0.049))
        spans.append(("serve.decode", b + 0.06, b + 0.09,
                      {"rows": 4 + k, "live_tokens": 5000 + 1000 * k,
                       "moe_held": 24 + 6 * k, "experts_hit": 52 + 26 * k}))
        mods.append(("jit_decode_step(9)", b + 0.061, b + 0.061 + 0.01 * (k + 1)))
    dev = tr.Device([(n, a, e) for n, a, e in mods], mods)
    trace = tr.Trace({0: dev}, [("bench.window", 0.0, 0.2)], 0.0, 0.2)
    return trace, program_trace.Program(sorted(spans, key=lambda s: s[1]))


def test_readers_with_known_values(cfg):
    trace, prog = _synthetic()
    program_trace.remember(trace, prog)
    peak = e2e.peak("TPU v5 lite")
    record = {"cfg": cfg, "peak": peak}
    f = flops_mla
    dec = [(4, 5000, 24, 52), (5, 6000, 30, 78)]
    least = [f.least_time(f.decode_flops(cfg, r, lt, mh), f.decode_bytes(cfg, lt, eh), peak)
             for r, lt, mh, eh in dec]
    assert _read("decode_roofline.mla", trace, record) == pytest.approx(
        100 * np.mean(least) / 0.015, rel=1e-9)
    pre = f.least_time(f.prefill_flops(cfg, 1024, 1500), f.prefill_bytes(cfg, 1024, 200),
                       peak)
    assert _read("prefill_roofline.mla", trace, record) == pytest.approx(
        100 * pre / 0.048, rel=1e-9)
    work = f.prefill_flops(cfg, 1024, 1500) + sum(
        f.decode_flops(cfg, r, lt, mh) for r, lt, mh, _ in dec)
    assert _read("dsv2l_mfu", trace, record) == pytest.approx(
        100 * work / (0.2 * 197e12), rel=1e-9)
    # (52 + 78) of 2 steps x 8 held x 26 expert layers
    assert _read("experts_hit_share.mla", trace, record) == pytest.approx(
        100 * 130 / (2 * 8 * 26), rel=1e-12)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(program_trace, "_SEEN", {})
    return tmp_path


@pytest.mark.parametrize("fixture", ["v5e_1chip", "v5e_4chip"])
def test_readers_read_nothing_on_a_trace_of_the_parent(fixture, trace_dir, cfg):
    """Traces of a program without the expert counters: nothing to read,
    nothing raised."""
    dst = trace_dir / "cell" / "plugins" / "profile" / "1" / "t.xplane.pb"
    dst.parent.mkdir(parents=True)
    dst.write_bytes((FIXTURES / f"{fixture}.xplane.pb").read_bytes())
    t = tr.load(FIXTURES / f"{fixture}.xplane.pb")
    record = {"cfg": cfg, "peak": e2e.peak("TPU v5 lite")}
    assert all(_read(m, t, record) is None for m in READERS)


# -- the driver -----------------------------------------------------------------

def test_driver_runs_the_cell_on_the_cpu():
    """The cell's run at a small size without a chip: every request gets
    its tokens, which agree with the reference."""
    from bench import run as bench_run

    work = common.workload("dsv2l-doc-open")
    work["prompt"].update(median=60, min=16, max=128, buckets=[32, 64, 128])
    work["output"].update(median=8, min=2, max=16)
    work.update(check_tokens=64, rate_per_s=10)
    out = bench_run.main(["--workload", "dsv2l-doc-open", "--seed", str(2**31 + 9),
                          "--seconds", "1.5"], need_chip=False, cfg=small_config(),
                         work=work)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 15
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_s", "itl_mean_ms"}
    assert out["checks"]["served_gap_sigma"]["value"] < 0.12
