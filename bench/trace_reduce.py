"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<i>``; on each, the line
``XLA Modules`` holds one event per execution of a jitted program and
``XLA Ops`` one event per operation.  Host planes hold the benchmark's
own spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``).

Busy time is the union of the op intervals inside the window, averaged
over the chips; the idle share is 1 − busy / window.  Each gap in that
union is named by the innermost ``bench.`` span that holds its midpoint.
A module's name is the jitted function's (``jit_<name>``), stripped of
the ``jit_`` prefix and any ``(...)``/``.n`` suffix.  An op event is named
by its HLO text; its label is the instruction's name without ``%`` and the
``.n`` suffix (``%cov_accum.1 = ... custom-call(...)`` is ``cov_accum``:
a Pallas kernel's call takes the kernel's name).  Op events nest (a
``while`` spans the ops of its body), so the busy union is unaffected
and the breakdown of ops leaves the control-flow containers out.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

SPAN_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


CONTAINERS = ("while", "conditional", "call")


def op_label(raw: str) -> str:
    name = raw.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def module_name(raw: str) -> str:
    name = raw.split("(")[0].strip()
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclass
class Device:
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Tuple[str, int, int]] = field(default_factory=list)


@dataclass
class TraceSummary:
    """What a trace says about one window; every time in seconds."""

    window: Interval
    devices: List[Device]
    spans: List[Tuple[str, int, int]]

    @property
    def covered(self) -> Interval:
        """The part of the window the trace holds.  When the profiler's
        buffers fill it drops every later event, so a trace whose last
        device event ends more than 1 s and 2% of the window before the
        window's end is read up to that event."""
        ends = [s + d for dev in self.devices
                for _, s, d in (dev.ops or dev.modules)]
        if not ends:
            return self.window
        last = min(max(ends), self.window[1])
        lo, hi = self.window
        if hi - last > max(1_000_000_000, 0.02 * (hi - lo)):
            return (lo, last)
        return self.window

    @property
    def truncated(self) -> bool:
        return self.covered != self.window

    @property
    def window_s(self) -> float:
        lo, hi = self.covered
        return (hi - lo) * 1e-9

    def _busy(self, dev: Device) -> List[Interval]:
        events = dev.ops or dev.modules
        return union(clip(((s, s + d) for _, s, d in events),
                          *self.covered))

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        tot = sum(sum(e - s for s, e in self._busy(d)) for d in self.devices)
        return tot * 1e-9 / len(self.devices)

    def module_seconds(self, names: Sequence[str]) -> Optional[float]:
        """Device seconds of the programs named ``names`` (module names
        as :func:`module_name` gives them), summed over the window and
        averaged over the chips; None when none ran."""
        want = set(names)
        tot, seen = 0, False
        for d in self.devices:
            for name, s, dur in d.modules:
                if module_name(name) in want:
                    lo, hi = max(s, self.window[0]), min(s + dur,
                                                         self.window[1])
                    if hi > lo:
                        tot += hi - lo
                        seen = True
        return tot * 1e-9 / max(len(self.devices), 1) if seen else None

    def module_count(self, names: Sequence[str]) -> int:
        want = set(names)
        return sum(1 for d in self.devices[:1] for name, s, _ in d.modules
                   if module_name(name) in want
                   and self.window[0] <= s < self.window[1])

    def op_seconds(self, pattern: str,
                   modules: Optional[Sequence[str]] = None
                   ) -> Optional[float]:
        """Device seconds of ops whose label matches ``pattern`` (a
        regular expression, searched from the label's start), optionally
        only inside the programs ``modules``; None when none ran."""
        rx = re.compile(pattern)
        tot, seen = 0, False
        for d in self.devices:
            spans = None
            if modules is not None:
                want = set(modules)
                spans = union((s, s + dur) for n, s, dur in d.modules
                              if module_name(n) in want)
            for name, s, dur in d.ops:
                if not rx.match(name):
                    continue
                if spans is not None and not _inside(spans, s):
                    continue
                lo, hi = max(s, self.window[0]), min(s + dur,
                                                     self.window[1])
                if hi > lo:
                    tot += hi - lo
                    seen = True
        return tot * 1e-9 / max(len(self.devices), 1) if seen else None

    def top_ops(self, n: int = 10) -> List[List]:
        """Device seconds by op label, control-flow containers left out;
        by program when the op line was not read."""
        if not any(d.ops for d in self.devices):
            return self.top_modules(n)
        tot: Dict[str, int] = {}
        for d in self.devices:
            for name, s, dur in d.ops:
                if name not in CONTAINERS and \
                        self.window[0] <= s < self.window[1]:
                    tot[name] = tot.get(name, 0) + dur
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def top_modules(self, n: int = 15) -> List[List]:
        tot: Dict[str, int] = {}
        for d in self.devices:
            for name, s, dur in d.modules:
                if self.window[0] <= s < self.window[1]:
                    key = module_name(name)
                    tot[key] = tot.get(key, 0) + dur
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        edges = [self.covered[0]] + [t for iv in busy for t in iv] \
            + [self.covered[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]

    def span_at(self, t: int) -> str:
        best = None
        for name, s, dur in self.spans:
            if s <= t < s + dur and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0] if best else "outside bench spans"


def _inside(spans: List[Interval], t: int) -> bool:
    lo, hi = 0, len(spans)
    while lo < hi:
        mid = (lo + hi) // 2
        if spans[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    return lo < len(spans) and spans[lo][0] <= t


def xplane_path(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return paths[-1]


def summarize(planes, window_span: str, ops: bool = True) -> TraceSummary:
    """``planes``: iterable of objects with ``name`` and ``lines``; each
    line has ``name`` and ``events`` with ``name``, ``start_ns`` and
    ``duration_ns`` (``jax.profiler.ProfileData`` planes, or plain
    stand-ins in tests).  ``window_span`` names the host span that is the
    window.  ``ops=False`` skips the op line, which programs made of
    millions of small ops (iterative eigensolvers) fill faster than it
    can be read: busy time then comes from the program events."""
    devices: List[Device] = []
    spans: List[Tuple[str, int, int]] = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = Device()
            for line in plane.lines:
                if line.name == OP_LINE and ops:
                    dev.ops = [(op_label(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events]
                elif line.name == MODULE_LINE:
                    dev.modules = [(e.name, int(e.start_ns),
                                    int(e.duration_ns))
                                   for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    wins = [(s, s + d) for name, s, d in spans if name == window_span]
    if not wins:
        raise ValueError(f"trace has no {window_span!r} span")
    return TraceSummary(window=wins[-1], devices=devices, spans=spans)


def load(trace_dir: str, window_span: str, ops: bool = True
         ) -> TraceSummary:
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path(trace_dir))
    return summarize(data.planes, window_span, ops)
