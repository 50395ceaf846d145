import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# hermetic accumulate routing (same pin as rma_hlo_counts.py): the config-
# routing checks below depend on the declared crossover, not the operator's
os.environ["RMA_ACC_BENCH_JSON"] = "/nonexistent"
os.environ.pop("RMA_ACC_CROSSOVER", None)
import sys; sys.path.insert(0, __import__("os").path.join(__import__("os").path.dirname(__file__), "..", "..", "src"))
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.rma import WindowConfig
from repro.kernels import (accumulate_signal, ring_accumulate, ring_put,
                           put_signal, ring_all_reduce)
from repro.kernels import ref as R
from repro import compat

N = 8
mesh = compat.make_mesh((N,), ("x",))
def run(f, *xs, out_specs=P("x")):
    return jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=out_specs, check_vma=False))(*xs)

x = jnp.arange(N*32, dtype=jnp.float32)
out = run(lambda s: ring_put(s, axis="x", axis_size=N), x)
expect = R.ring_put_ref(np.arange(N*32, dtype=np.float32).reshape(N,32), axis_size=N)
np.testing.assert_allclose(np.asarray(out).reshape(N,32), expect)
print("ring_put OK")

flag = jnp.arange(N, dtype=jnp.float32) + 100
def ps(s):
    f = jax.lax.axis_index("x").astype(jnp.float32)[None] + 100
    d, fl = put_signal(s, f, axis="x", axis_size=N, ordered=True)
    return jnp.concatenate([d, fl])
out = np.asarray(run(ps, x)).reshape(N, 33)
np.testing.assert_allclose(out[:, :32], expect)
np.testing.assert_allclose(out[:, 32], np.roll(np.arange(N)+100, 1))
print("put_signal ordered OK")
def ps2(s):
    f = jax.lax.axis_index("x").astype(jnp.float32)[None] + 100
    d, fl = put_signal(s, f, axis="x", axis_size=N, ordered=False)
    return jnp.concatenate([d, fl])
out = np.asarray(run(ps2, x)).reshape(N, 33)
np.testing.assert_allclose(out[:, :32], expect)
print("put_signal unordered OK")

# --- NIC-atomic accumulate (the P3 latency path, kernels/intrinsic.py)
buf = jnp.arange(N*16, dtype=jnp.float32)
upd = jnp.arange(N*4, dtype=jnp.float32) * 0.5
for op in ("sum", "min", "max", "replace"):
    out = run(lambda b, u, op=op: ring_accumulate(
        u, b, axis="x", axis_size=N, op=op, offset=2), buf, upd)
    expect = R.ring_accumulate_ref(buf.reshape(N,16), upd.reshape(N,4),
                                   axis_size=N, op=op, offset=2)
    np.testing.assert_allclose(np.asarray(out).reshape(N,16), np.asarray(expect))
print("ring_accumulate (sum/min/max/replace) OK")

# the WindowConfig that routes intrinsic must lower here; one that routes
# tiled must be rejected (one declaration drives both layers)
cfg_ok = WindowConfig(same_op="sum", max_atomic_elems=8)
out = run(lambda b, u: ring_accumulate(u[:4], b, axis="x", axis_size=N,
                                       config=cfg_ok), buf, upd)
try:
    def bad(b, u):
        return ring_accumulate(u, b, axis="x", axis_size=N,
                               config=WindowConfig(same_op="sum", max_atomic_elems=1))
    run(bad, buf, upd)
    raise SystemExit("FAIL: tiled-routed config accepted by the atomic kernel")
except ValueError:
    print("ring_accumulate config routing check OK")

# --- fused accumulate+signal (ordered_put_signal.py)
for ordered in (True, False):
    def acs(b, u, ordered=ordered):
        fv = jax.lax.axis_index("x").astype(jnp.float32)[None] + 100
        o, fl = accumulate_signal(u, b, fv, axis="x", axis_size=N, op="max",
                                  offset=0, ordered=ordered)
        return jnp.concatenate([o, fl])
    out = np.asarray(run(acs, buf, upd)).reshape(N, 17)
    expect = R.ring_accumulate_ref(buf.reshape(N,16), upd.reshape(N,4),
                                   axis_size=N, op="max", offset=0)
    np.testing.assert_allclose(out[:, :16], np.asarray(expect))
    np.testing.assert_allclose(out[:, 16], np.roll(np.arange(N)+100, 1))
print("accumulate_signal both orders OK")

xr = jax.random.normal(jax.random.PRNGKey(0), (N*13,))
out = np.asarray(run(lambda s: ring_all_reduce(s, axis="x", axis_size=N), xr))
expect = np.tile(np.asarray(xr).reshape(N,13).sum(0), (N,1)).reshape(-1)
np.testing.assert_allclose(out, expect, rtol=1e-5)
print("ring_all_reduce OK")
print("RMA KERNELS OK")
