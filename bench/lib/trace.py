"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-program device time and exposed collective time.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run (named by its HLO text), ``Async XLA Ops`` one
per asynchronous operation from start to done, and ``XLA Modules`` one
per program run.
The harness's own spans (``bench.*``) sit on the host plane, on the same
clock.  The traced slice is the harness's ``bench.window`` span.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = ("collective-permute", "all-reduce", "all-gather",
              "reduce-scatter", "all-to-all")


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals ``(n, 2)`` as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=float)


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The parts of the disjoint intervals ``a`` that no interval of the
    disjoint intervals ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out, dtype=float).reshape(-1, 2)


def base_name(name: str) -> str:
    """An operation's name without its instance number: ``fusion`` for
    ``fusion.12`` or for the trace's full HLO text ``%fusion.12 = ...``."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


@dataclasses.dataclass
class Device:
    ops: list            # [(name, start_s, end_s)]
    modules: list        # [(name, start_s, end_s)]
    async_ops: list = dataclasses.field(default_factory=list)  # start to done


@dataclasses.dataclass
class Trace:
    devices: dict        # device id -> Device
    spans: list          # [(name, start_s, end_s)] host spans named bench.*
    t0: float            # the traced slice, seconds on the trace's clock
    t1: float

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def op_intervals(self, dev: int, pred=None) -> np.ndarray:
        iv = [(s, e) for n, s, e in self.devices[dev].ops
              if pred is None or pred(n)]
        return clip(merge(np.asarray(iv, float).reshape(-1, 2)),
                    self.t0, self.t1)

    def busy_s(self, dev: int) -> float:
        """Seconds of the slice in which some operation ran on ``dev``."""
        return length(self.op_intervals(dev))

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s(d) for d in self.devices]))

    def idle_share(self) -> float:
        """1 - busy / slice, averaged over the devices."""
        return 1.0 - self.mean_busy_s() / self.window_s

    def programs(self, dev: int, part: str) -> list[float]:
        """Device seconds of each run of the programs whose name holds
        ``part``, inside the slice."""
        return [e - s for n, s, e in self.devices[dev].modules
                if part in n and s >= self.t0 and e <= self.t1]

    def collective(self, dev: int, kind: str = "collective-permute"):
        """(seconds some ``kind`` operation was in flight, seconds of that
        in which no other operation ran) on ``dev`` inside the slice.  In
        flight: its op events, and its asynchronous start-to-done spans."""
        d = self.devices[dev]
        iv = [(s, e) for n, s, e in d.ops + d.async_ops if kind in base_name(n)]
        coll = clip(merge(np.asarray(iv, float).reshape(-1, 2)), self.t0, self.t1)
        other = self.op_intervals(
            dev, lambda n: not any(c in base_name(n) for c in COLLECTIVE))
        return length(coll), length(subtract(coll, other))

    def top_ops(self, dev: int, k: int = 10) -> list:
        tot: dict[str, float] = {}
        for n, s, e in self.devices[dev].ops:
            if s >= self.t0 and e <= self.t1:
                b = base_name(n)
                tot[b] = tot.get(b, 0.0) + (e - s)
        return sorted(([n, t] for n, t in tot.items()), key=lambda x: -x[1])[:k]

    def idle_gaps(self, dev: int, k: int = 10) -> list:
        """The longest gaps with no operation on ``dev``, each named by the
        innermost harness span that covers most of it."""
        busy = self.op_intervals(dev)
        gaps = subtract(np.asarray([[self.t0, self.t1]]), busy)
        out = []
        for s, e in gaps:
            best, cover, best_len = "none", 0.0, np.inf
            for n, a, b in self.spans:
                c = min(b, e) - max(a, s)
                if n == "bench.window":
                    continue
                if c > cover or (c == cover and c > 0 and b - a < best_len):
                    best, cover, best_len = n, c, b - a
            out.append([best, float(e - s)])
        return sorted(out, key=lambda x: -x[1])[:k]


def find(run_dir: Path) -> Path:
    found = sorted(Path(run_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {run_dir}")
    return found[-1]


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events] for line in plane.lines}
            devices[int(m.group(1))] = Device(lines.get("XLA Ops", []),
                                              lines.get("XLA Modules", []),
                                              lines.get("Async XLA Ops", []))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in line.events if e.name.startswith("bench.")]
    win = [s for s in spans if s[0] == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    return Trace(devices, spans, win[-1][1], win[-1][2])
