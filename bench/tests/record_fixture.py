#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

  python3 bench/tests/record_fixture.py <out.xplane.pb>

On every chip JAX sees: three steps of a jitted matmul chain, a gap on
the host between them, and, with more than one chip, a ring shift of the
result (a ``collective-permute``) behind each.  The harness's spans
(``bench.window``, ``bench.step``) mark the slice and the steps.  Needs a
TPU; the file is committed as a fixture.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from bench.lib import common, trace  # noqa: E402


def main(out: str) -> None:
    common.require_devices(1)
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(devices, ("x",))
    x = jax.device_put(jnp.ones((n * 1024, 2048), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P("x")))
    w = jax.device_put(jnp.ones((2048, 2048), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P()))

    def body(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        if n > 1:
            x = jax.lax.ppermute(x, "x", [(i, (i + 1) % n) for i in range(n)])
        return x

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("x"), P()),
                                 out_specs=P("x")))
    step(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with common.span("bench.window"):
        for _ in range(3):
            with common.span("bench.step"):
                x = step(x, w)
                x.block_until_ready()
            with common.span("bench.wait_due"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = trace.find(Path(tmp))
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    t = trace.load(Path(out))
    print(f"{n} device(s), slice {t.window_s:.6f} s, busy {t.mean_busy_s():.6f} s, "
          f"collective {[t.collective(d) for d in t.devices]}, "
          f"programs {[len(t.programs(d, 'jit')) for d in t.devices]}, "
          f"{Path(out).stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
