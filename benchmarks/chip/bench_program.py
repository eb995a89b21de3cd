"""The program's own spans (``repro.core.trace``), read from inside the
program: for the per-layer readers, and to split a traced window's device
idle time by what the dispatching thread was doing.

While a profiler session collects host events the program keeps its spans
in memory as well as in the trace, so a ``--trace 1`` run holds the spans
of its window: ``spans(rec)`` drains them once into ``rec.program``. A
program without ``repro.core.trace`` (or a run without a trace) gives an
empty list, and the readers then return None.

Spans of one save share its step as their request id; spans of one
restore share the restore's id.

``attribute_dispatch_gaps`` works on plain lists, like ``bench_trace``:
each device idle gap goes to the innermost ``cnr.*`` span open on the
dispatching thread (the one that holds ``bench.window``), failing that to
that thread's innermost ``bench.*`` span, failing that to ``host.other``.
``read_host_lines`` gives each host thread's ``cnr.*`` and ``bench.*``
events from a ``.xplane.pb``, one list per thread.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float, str]
SAVE_SPANS = ("cnr.snapshot.drain", "cnr.snapshot.copy", "cnr.save.wait",
              "cnr.save.encode", "cnr.save.quant", "cnr.save.write",
              "cnr.save.commit", "cnr.save")


def spans(rec) -> list:
    """The program's spans of the traced window, drained once into
    ``rec.program``."""
    if getattr(rec, "program", None) is None:
        rec.program = []
        if rec.trace is not None:
            try:
                from repro.core import trace
            except ImportError:
                pass
            else:
                rec.program = trace.drain()
    return rec.program


def per_save(rec, name: str, attr: Optional[str] = None) -> Dict[int, float]:
    """For each of the window's committed saves that has spans, the summed
    seconds (or ``attr``) of its spans named ``name``."""
    steps = {s["step"] for s in rec.saves}
    out: Dict[int, float] = {}
    found = set()
    for sp in spans(rec):
        if sp.request in steps:
            found.add(sp.request)
            if sp.name == name:
                v = sp.seconds if attr is None else sp.attrs.get(attr, 0)
                out[sp.request] = out.get(sp.request, 0.0) + v
    return {s: out.get(s, 0.0) for s in found}


def per_restore(rec, name: str) -> Dict[int, float]:
    """For each restore of the window, the summed seconds of its spans named
    ``name``."""
    ids = {sp.request for sp in spans(rec) if sp.name == "cnr.restore"}
    out = {i: 0.0 for i in ids}
    for sp in spans(rec):
        if sp.name == name and sp.request in out:
            out[sp.request] += sp.seconds
    return out


def mean(values: Dict[int, float]) -> Optional[float]:
    return sum(values.values()) / len(values) if values else None


def rate_gbps(rec, name: str) -> Optional[float]:
    """Summed ``bytes`` over summed seconds of the spans named ``name``, in
    GB/s."""
    chosen = [sp for sp in spans(rec) if sp.name == name]
    secs = sum(sp.seconds for sp in chosen)
    if not chosen or secs <= 0:
        return None
    return sum(sp.attrs.get("bytes", 0) for sp in chosen) / secs / 1e9


# ------------------------------------------------- the dispatching thread
def read_host_lines(trace_dir: str) -> List[List[Interval]]:
    """Each host thread's ``cnr.*`` and ``bench.*`` events from the newest
    ``.xplane.pb`` under ``trace_dir``, in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(paths[-1])
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name) for e in line.events
                   if e.name.startswith(("cnr.", "bench."))]
            if evs:
                lines.append(evs)
    return lines


def dispatch_line(lines: List[List[Interval]],
                  window: str = "bench.window") -> List[Interval]:
    """The events of the thread that holds ``window``."""
    for evs in lines:
        if any(n == window for _, _, n in evs):
            return evs
    raise ValueError(f"no thread holds {window!r}")


def attribute_dispatch_gaps(gaps: List[Tuple[float, float]],
                            events: List[Interval],
                            window: str = "bench.window"
                            ) -> Dict[str, float]:
    """Idle seconds by the innermost ``cnr.*`` span open on the dispatching
    thread over each part of a gap, failing that its innermost ``bench.*``
    span, failing that ``host.other``. ``events`` are that thread's."""
    inner = [ev for ev in events if ev[2] != window]
    cuts = sorted({t for s, e, _ in inner for t in (s, e)})
    names: List[str] = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        open_ = [ev for ev in inner if ev[0] <= mid < ev[1]]
        prog = [ev for ev in open_ if ev[2].startswith("cnr.")]
        pick = prog or open_
        names.append(min(pick, key=lambda ev: ev[1] - ev[0])[2]
                     if pick else "host.other")
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
