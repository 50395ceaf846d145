"""The program's own spans in a traced slice: the serving engine's
``serve.*`` host spans with their integer arguments, the offset between
the host clock they are on and the device clock, and the device's idle
gaps named by what the host was doing in them.

The harness's :class:`bench.lib.trace.Trace` keeps only its own
``bench.*`` spans; :func:`of` reads the same ``.xplane.pb`` again for the
program's, once per traced slice, and writes the clock offset and the
named idle gaps into the run's log.  A program without these spans gives
an empty list, and every reader of it nothing.

The offset δ (host time − device time of one instant) is bounded by the
``decode_step`` program runs: the device cannot start one before
``serve.decode`` dispatched it (the span opens at the jit call), so δ ≥
span start − run start; the host cannot read the tokens before the
``argmax`` program that follows the run ends, so δ ≤
``serve.decode.sync`` end − that program's end.  Each run is paired with
the ``serve.decode`` that starts nearest to it, which holds while the
skew is far below a decode step.  An idle gap is named at both bounds,
and a gap the two name differently is given both names, ``a|b``: it is
not attributed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import common

PREFIX = "serve."
SYNC = ".sync"
DECODE_PROGRAM = "decode_step"
ARGMAX_PROGRAM = "argmax"    # reads the tokens out of the run
ARGMAX_WITHIN = 3            # programs after the run: slice, argmax, cast


@dataclasses.dataclass
class Program:
    spans: list          # [(name, start_s, end_s, args)] host clock, by start
    bounds: tuple | None = None   # (lo, hi) of δ in seconds, from `runs` runs
    runs: int = 0

    @property
    def clock_offset_s(self):
        return None if self.bounds is None else 0.5 * sum(self.bounds)

    def inside(self, name: str, t0: float, t1: float) -> list:
        """The spans named ``name`` wholly inside ``[t0, t1]``."""
        return [s for s in self.spans if s[0] == name and s[1] >= t0 and s[2] <= t1]

    def steps(self, trace) -> list:
        """The ``serve.step`` spans wholly inside the traced slice."""
        return self.inside("serve.step", trace.t0, trace.t1)

    def admit_waits(self, trace) -> list:
        """For each ``serve.prefill`` wholly inside the traced slice whose
        request's ``serve.submit`` is in the trace, the seconds between
        the two starts: its wait in the scheduler."""
        submitted = {}
        for s in self.spans:                 # by start: the latest wins
            if s[0] == "serve.submit":
                submitted[s[3]["rid"]] = s[1]
        return [s[1] - submitted[s[3]["rid"]]
                for s in self.inside("serve.prefill", trace.t0, trace.t1)
                if submitted.get(s[3]["rid"], np.inf) <= s[1]]

    def syncs_in(self, step) -> list:
        """The host's waits for a device result inside ``step``."""
        return [s for s in self.spans if s[0].endswith(SYNC)
                and s[1] >= step[1] and s[2] <= step[2]]


def load(path) -> tuple[list, tuple | None]:
    """The ``serve.*`` host spans of a trace file, by start, and the
    ``(start, end)`` of its last ``bench.window`` span."""
    from jax.profiler import ProfileData

    spans, window = [], None
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  {k: v for k, v in e.stats}))
                elif e.name == "bench.window":
                    window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
    return sorted(spans, key=lambda s: s[1]), window


def clock_bounds(spans: list, modules: list, t0: float, t1: float):
    """``((lo, hi), runs)``: the bounds on δ that the ``decode_step`` runs
    starting inside ``[t0, t1]`` give, or ``(None, 0)``.  The upper bound
    takes the end of the ``argmax`` run among the few programs next after
    each, or the ``decode_step`` run's own end where none is there."""
    decodes = [s for s in spans if s[0] == "serve.decode"]
    syncs = [s for s in spans if s[0] == "serve.decode" + SYNC]
    modules = sorted(modules, key=lambda m: m[1])
    lo, hi, runs = -np.inf, np.inf, 0
    for i, (name, start, end) in enumerate(modules):
        if DECODE_PROGRAM not in name or not t0 <= start <= t1 or not decodes:
            continue
        d = min(decodes, key=lambda s: abs(s[1] - start))
        sync = [y for y in syncs if y[1] >= d[1] and y[2] <= d[2]]
        if not sync:
            continue
        read = [m for m in modules[i + 1:i + 1 + ARGMAX_WITHIN]
                if ARGMAX_PROGRAM in m[0]]
        if read:
            end = read[0][2]
        lo, hi, runs = max(lo, d[1] - start), min(hi, sync[-1][2] - end), runs + 1
    return ((float(lo), float(hi)), runs) if runs else (None, 0)


def _named_gaps(trace, dev: int, program: Program, delta: float) -> list:
    """Every idle gap of ``dev``, longest first, named by the innermost
    span that covers most of it with the host spans moved by ``delta``
    onto the device clock."""
    spans = [(n, a - delta, b - delta) for n, a, b in trace.spans]
    spans += [(n, a - delta, b - delta) for n, a, b, _ in program.spans]
    return dataclasses.replace(trace, spans=spans).idle_gaps(dev, k=None)


def idle_gaps(trace, dev: int, program: Program, k: int = 10) -> list:
    """The ``k`` longest gaps with no operation on ``dev`` (as
    :meth:`Trace.idle_gaps` finds them), each named by the innermost span,
    the harness's or the program's, that covers most of it once the host
    spans are moved onto the device clock, at both bounds of δ: ``a|b``
    where the two bounds name it differently."""
    lo, hi = program.bounds or (0.0, 0.0)
    at_lo, at_hi = (_named_gaps(trace, dev, program, d) for d in (lo, hi))
    # one order of the same gaps: the sort by length is stable
    return [[a if a == b else f"{a}|{b}", n]
            for (a, n), (b, _) in zip(at_lo[:k], at_hi[:k])]


def build(trace, path) -> Program | None:
    """The program's spans of the trace file ``path``, if it holds the
    slice of ``trace``."""
    spans, window = load(path)
    if window != (trace.t0, trace.t1):
        return None
    prog = Program(spans)
    if spans and trace.devices:
        dev = min(trace.devices)
        prog.bounds, prog.runs = clock_bounds(spans, trace.devices[dev].modules,
                                              trace.t0, trace.t1)
    return prog


def note(trace, program: Program) -> None:
    harness = sum(1 for n, a, b in trace.spans
                  if n == "bench.step" and a >= trace.t0 and b <= trace.t1)
    prefills = program.inside("serve.prefill", trace.t0, trace.t1)
    common.note(f"[program] {len(program.steps(trace))} serve.step and "
                f"{harness} bench.step spans in the slice; "
                f"{len(prefills)} serve.prefill, "
                f"{len(program.admit_waits(trace))} of them with their "
                f"serve.submit")
    if not program.spans:
        return
    if program.bounds is not None:
        lo, hi = program.bounds
        argmax = sum(1 for n, a, _ in trace.devices[min(trace.devices)].modules
                     if ARGMAX_PROGRAM in n and trace.t0 <= a <= trace.t1)
        common.note(f"[clock] host minus device clock {program.clock_offset_s!r} s, "
                    f"bounds {lo!r} to {hi!r} s, from {program.runs} "
                    f"{DECODE_PROGRAM} runs and {argmax} {ARGMAX_PROGRAM} runs")
    if trace.devices:
        common.note(f"[gaps] the longest idle gaps by program span at both "
                    f"bounds of the clock offset: "
                    f"{idle_gaps(trace, min(trace.devices), program)}")


_SEEN: dict = {}


def of(trace) -> Program | None:
    """The program's spans in ``trace``'s slice, read from the newest trace
    file under the harness's trace directory that holds that slice; the
    first call for a slice writes the clock offset and named gaps into the
    run's log.  Nothing where no file holds the slice."""
    key = (trace.t0, trace.t1)
    if key not in _SEEN:
        found = sorted(common.TRACE_DIR.glob("*/plugins/profile/*/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime, reverse=True)
        prog = next((p for p in (build(trace, f) for f in found) if p), None)
        if prog is not None:
            note(trace, prog)
        _SEEN[key] = prog
    return _SEEN[key]


def remember(trace, program: Program) -> None:
    """Give ``trace``'s slice its program spans without a file (tests)."""
    _SEEN[(trace.t0, trace.t1)] = program
