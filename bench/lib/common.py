"""Files, devices, the compile cache, compile counting, spans and the
result line."""
from __future__ import annotations

import importlib.util
import time
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


class BenchError(SystemExit):
    """Ends the run with a non-zero exit and no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no workload file {path.relative_to(ROOT)}")
    return load_json(path)


def config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no configuration file {path.relative_to(ROOT)}")
    return load_json(path)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    path = BENCH / "drivers" / f"{kind}.py"
    if not path.is_file():
        raise BenchError(f"no driver {path.relative_to(ROOT)}")
    return load_module(path, f"bench_driver_{kind}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no metric reader {path.relative_to(ROOT)}")
    return load_module(path, "bench_metric_" + name.replace(".", "_"))


def use_program() -> None:
    """Make the system under test importable (``src/`` of the checkout)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_devices(count: int) -> list:
    """The devices of this run: TPUs, at least ``count`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX sees {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < count:
        raise BenchError(f"the cell needs {count} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices[:count]


def place_compile_cache() -> str:
    """JAX's persistent compilation cache at ``CACHE_DIR``, whatever the
    environment says, holding every program however fast it compiled, and
    never evicting (a size limit set from outside turns on an eviction
    path that can leave every later write failing)."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts the programs JAX lowers (each new program is lowered once,
    whether it is then compiled or read from the persistent cache)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


def span(name: str):
    """A host span in the profiler's trace (free while no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_memory(devices) -> int:
    """``peak_bytes_in_use`` on the fullest of ``devices`` (0 off the
    chip, where tests run)."""
    if devices[0].platform != "tpu":
        return 0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    if any(p is None for p in peaks):
        raise BenchError("a device reports no peak_bytes_in_use")
    return int(max(peaks))


def device_record(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def note(msg: str) -> None:
    """A line of the run's log (standard output, before the result)."""
    print(msg, flush=True)


def checks_pass(checks: dict) -> bool:
    """Every number compared at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


class TraceSlice:
    """The traced slice of a ``--trace 1`` run: the profiler starts and
    stops between steps, ``start_s`` into the window and ``seconds`` long
    (the workload's ``trace``); ``t_on`` and ``t_off`` are on the host
    clock the drivers use."""

    def __init__(self, ctx, t_window: float):
        tr = ctx.work["trace"]
        self.on = bool(ctx.trace)
        self.start = t_window + tr["start_s"]
        self.stop = self.start + tr["seconds"]
        self.state = 0
        self.dir = ctx.trace_dir
        self.span = None
        self.t_on = self.t_off = None

    def tick(self, now: float) -> None:
        import jax

        if not self.on:
            return
        if self.state == 0 and now >= self.start:
            jax.profiler.start_trace(str(self.dir))
            self.span = span("bench.window")
            self.span.__enter__()
            self.t_on = time.perf_counter()
            self.state = 1
        elif self.state == 1 and now >= self.stop:
            self.close()

    def close(self) -> None:
        import jax

        if self.state == 1:
            self.t_off = time.perf_counter()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = 2
