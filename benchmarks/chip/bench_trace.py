"""Reduction of a profiler trace to the benchmark's device numbers.

``read_xplane`` turns the JAX profiler's ``.xplane.pb`` into plain lists;
the rest works on those lists alone, so it is checked on small made-up
traces. Times are in seconds on the trace's own clock, on which the
device's events and the host's spans both lie.

* chips: a chip is a device plane with events on its ``XLA Ops`` line;
  planes without ops (``/device:CUSTOM:Megascale Trace``) are left out;
* device ops: a chip's busy time is the union of its ops' intervals;
  ``busy_s`` is the mean over the chips, so the idle share is the mean
  per-chip idle share; idle gaps are the stretches in which every chip is
  idle;
* modules: events of the ``XLA Modules`` line, one per execution of a
  compiled program, named after the jitted function (``train_step``,
  ``quant_pack_pallas``, ``chunk_hash_pallas`` ...), summed over the chips
  in chip-seconds;
* host spans: the benchmark's own ``TraceAnnotation`` spans, all named
  ``bench.<what>``, from the host threads.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
from typing import Dict, List, Tuple

Interval = Tuple[float, float, str]          # (start s, end s, name)

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: List[List[Interval]]                 # per device
    modules: List[List[Interval]]             # per device
    spans: List[Interval]                     # host, bench.* only


def read_xplane(trace_dir: str) -> Trace:
    """Load the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                target = {"XLA Ops": dev_ops,
                          "XLA Modules": dev_mods}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    target.append((s, s + e.duration_ns * 1e-9, e.name))
            ops.append(dev_ops)
            modules.append(dev_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name))
    return Trace(ops=ops, modules=modules, spans=spans)


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals: List[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def idle_gaps(intervals: List[Interval], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no interval runs."""
    gaps, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def seconds_by_name(intervals: List[Interval], lo: float, hi: float,
                    key=lambda name: name) -> Dict[str, float]:
    """Summed duration (clipped to [lo, hi]) per ``key(name)``."""
    out: Dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            k = key(name)
            out[k] = out.get(k, 0.0) + d
    return out


def module_key(name: str) -> str:
    """``jit_train_step(123)`` and ``jit(train_step)`` -> ``train_step``."""
    base = name.split("(")[0] if not name.startswith("jit(") else name[4:]
    base = base.rstrip(")")
    return base[4:] if base.startswith("jit_") else base


def attribute_gaps(gaps: List[Tuple[float, float]], spans: List[Interval]
                   ) -> Dict[str, float]:
    """Idle seconds by the innermost host span open over each part of a gap
    (``host.other`` where none is)."""
    # sweep the spans once: between consecutive span boundaries the set of
    # open spans is fixed, and the shortest open one is the innermost
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    names: List[str] = []
    heap: List[tuple] = []
    j = 0
    for a in cuts[:-1]:
        while j < len(by_start) and by_start[j][0] <= a:
            s, e, n = by_start[j]
            heapq.heappush(heap, (e - s, e, n))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        names.append(heap[0][2] if heap else "host.other")
    out: Dict[str, float] = {}

    def add(name: str, d: float) -> None:
        if d > 0:
            out[name] = out.get(name, 0.0) + d

    for g0, g1 in gaps:
        if not names or g1 <= cuts[0] or g0 >= cuts[-1]:
            add("host.other", g1 - g0)
            continue
        add("host.other", max(0.0, min(g1, cuts[0]) - g0))
        add("host.other", max(0.0, g1 - max(g0, cuts[-1])))
        i = max(bisect.bisect_right(cuts, g0) - 1, 0)
        while i < len(names) and cuts[i] < g1:
            add(names[i], min(g1, cuts[i + 1]) - max(g0, cuts[i]))
            i += 1
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                         # mean over the chips
    module_s: Dict[str, float]            # summed over the chips
    gaps_by_span: Dict[str, float]
    n_chips: int = 1                      # device planes that hold ops

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(tr: Trace, window: str = "bench.window") -> Summary:
    """Reduce a trace over the host span named ``window``."""
    wins = [(s, e) for s, e, n in tr.spans if n == window]
    if not wins:
        raise ValueError(f"no {window!r} span in the trace")
    lo, hi = wins[0]
    chips = [i for i, o in enumerate(tr.ops) if o]
    busy = sum(busy_seconds(tr.ops[i], lo, hi) for i in chips) / max(
        len(chips), 1)
    mods: Dict[str, float] = {}
    for dev in tr.modules:
        for k, v in seconds_by_name(dev, lo, hi, module_key).items():
            mods[k] = mods.get(k, 0.0) + v
    gaps = idle_gaps([iv for o in tr.ops for iv in o], lo, hi)
    inner = [sp for sp in tr.spans if sp[2] != window]
    return Summary(window_s=hi - lo, busy_s=busy, module_s=mods,
                   gaps_by_span=attribute_gaps(gaps, inner),
                   n_chips=len(chips))
