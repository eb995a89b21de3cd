#!/usr/bin/env python3
"""One run of one cell, with the splits the program's spans give.

    python3 benchmarks/chip/trace_split.py --workload <name> --seed <n> \\
        --seconds <s> [--trace 1] [--record 0|1]

With ``--trace 1`` it runs the cell as ``run.py --trace 1`` does and, from
the same profiler trace, adds under ``split``:

* ``idle_by_dispatch_span``: the window's device idle seconds by the
  innermost ``cnr.*`` span open on the dispatching thread, then its
  ``bench.*`` span, then ``host.other`` (``bench_program``);
* ``compiles_by_span``: backend compiles in the window by the innermost
  program span open on the compiling thread;
* ``sums``: the stall, write wall and restore rebuilt from their parts:
  drain + copy + wait and the snapshot release beside ``save_stall_s``,
  the mean ``cnr.save`` span and its commit beside ``write_wall_s``
  (which ends before the commit), the mean ``cnr.restore`` span beside
  ``restore_host_s``;
* ``device_planes``: each device plane's name, lines, ops busy and module
  seconds;
* ``train_step_gaps``: for the ``train_step`` executions, the module time
  in which no ``XLA Ops`` event runs, and which events of the device
  plane's other lines lie in it.

With ``--trace 0 --record 1`` it runs the cell with the program's span
recording on (``repro.core.trace.record()``) and reports its end-to-end
metrics, to price recording against a plain ``run.py`` run.

The result line is the last line of standard output.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import bench_harness  # noqa: E402
import bench_program  # noqa: E402
import bench_trace  # noqa: E402


def read_device_lines(trace_dir: str) -> List[Dict[str, list]]:
    """Per device plane: every line's events, by line name (and the plane's
    name under ``"plane"``)."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines: Dict[str, list] = {"plane": plane.name}
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events)
            out.append(lines)
    return out


def subtract(spans: List[Tuple[float, float]],
             cover: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``spans`` minus the merged, sorted intervals ``cover``."""
    ends = [b for _, b in cover]
    out = []
    for s, e in spans:
        t = s
        i = bisect.bisect_right(ends, t)
        while i < len(cover) and cover[i][0] < e:
            a, b = cover[i]
            if a > t:
                out.append((t, a))
            t = max(t, b)
            i += 1
        if t < e:
            out.append((t, e))
    return out


def overlap_by_name(events, holes) -> Dict[str, float]:
    """Seconds of each event name that lie inside the sorted ``holes``."""
    starts = [a for a, _ in holes]
    out: Dict[str, float] = {}
    for s, e, name in events:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(holes) and holes[i][0] < e:
            d = min(e, holes[i][1]) - max(s, holes[i][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            i += 1
    return out


def train_step_gaps(devices, lo: float, hi: float) -> dict:
    """What lies in the ``train_step`` module time outside the ops' union."""
    mod_s = hole_s = 0.0
    by_line: Dict[str, Dict[str, float]] = {}
    for lines in devices:
        mods = [(max(s, lo), min(e, hi)) for s, e, n in
                lines.get("XLA Modules", [])
                if bench_trace.module_key(n) == "train_step"
                and min(e, hi) > max(s, lo)]
        mods.sort()
        ops = bench_trace.union(lines.get("XLA Ops", []), lo, hi)
        holes = subtract(mods, ops)
        mod_s += sum(e - s for s, e in mods)
        hole_s += sum(e - s for s, e in holes)
        for name, evs in lines.items():
            if name in ("plane", "XLA Ops", "XLA Modules"):
                continue
            got = overlap_by_name(evs, holes)
            if got:
                d = by_line.setdefault(name, {})
                for k, v in got.items():
                    d[k] = d.get(k, 0.0) + v
    return {"module_s": mod_s, "outside_ops_s": hole_s,
            "other_lines": {ln: bench_trace.top(d, 8)
                            for ln, d in by_line.items()}}


class SplitRun(bench_harness.Run):
    """The harness's run, reading the trace's host threads and device
    lines before the harness removes the trace."""

    last = None

    def execute(self):
        SplitRun.last = self
        real = bench_trace.read_xplane

        def read_and_keep(trace_dir):
            self.host_lines = bench_program.read_host_lines(trace_dir)
            self.device_lines = read_device_lines(trace_dir)
            return real(trace_dir)

        bench_trace.read_xplane = read_and_keep
        try:
            return super().execute()
        finally:
            bench_trace.read_xplane = real


def split(run: SplitRun, metrics: dict) -> dict:
    from repro.core import trace

    rec = run.rec
    events = bench_program.dispatch_line(run.host_lines)
    (lo, hi), = [(s, e) for s, e, n in events if n == "bench.window"]
    ops = [iv for dev in run.device_lines for iv in dev.get("XLA Ops", [])]
    # the same idle gaps as bench_trace.summarize: the union over planes
    idle = bench_program.attribute_dispatch_gaps(
        bench_trace.idle_gaps(ops, lo, hi), events)
    spans = bench_program.spans(rec)
    ids = {sp.id for sp in spans}
    child = {}
    for sp in spans:
        if sp.parent_id in ids:
            child[sp.parent_id] = child.get(sp.parent_id, 0) + sp.compiles
    compiles: Dict[str, int] = {}
    for sp in spans:
        own = sp.compiles - child.get(sp.id, 0)
        if own:
            compiles[sp.name] = compiles.get(sp.name, 0) + own
    val = {k: v["value"] for k, v in metrics.items()}
    sums = {}
    if rec.saves:
        parts = ("boundary_drain_s", "snapshot_copy_s", "save_wait_s")
        if all(p in val for p in parts):
            sums["drain+copy+wait_s"] = sum(val[p] for p in parts)
        sums["release_s"] = bench_program.mean(
            bench_program.per_save(rec, "cnr.snapshot.release"))
        sums["cnr.checkpoint_mean_s"] = bench_program.mean(
            bench_program.per_save(rec, "cnr.checkpoint"))
        sums["save_stall_s"] = val.get("save_stall_s")
        sums["cnr.save_mean_s"] = bench_program.mean(
            bench_program.per_save(rec, "cnr.save"))
        # the manifest's wall_time_s is read before the commit
        sums["commit_s"] = val.get("commit_s")
        sums["write_wall_s"] = val.get("write_wall_s")
    if rec.restores:
        sums["cnr.restore_mean_s"] = bench_program.mean(
            bench_program.per_restore(rec, "cnr.restore"))
        sums["restore_host_s"] = val.get("restore_host_s")
    out = {"idle_by_dispatch_span": bench_trace.top(idle, 12),
           "idle_s": sum(idle.values()),
           "compiles_by_span": compiles,
           "spans_kept": len(spans), "spans_dropped": trace.dropped(),
           "sums": sums}
    out["device_planes"] = [
        {"plane": dev["plane"],
         "ops_busy_s": bench_trace.busy_seconds(dev.get("XLA Ops", []),
                                                lo, hi),
         "module_s": sum(bench_trace.seconds_by_name(
             dev.get("XLA Modules", []), lo, hi).values()),
         "lines": sorted(k for k in dev if k != "plane")}
        for dev in run.device_lines]
    if rec.saves:
        out["train_step_gaps"] = train_step_gaps(run.device_lines, lo, hi)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--record", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.core import trace

    bench_harness.Run = SplitRun
    if args.record:
        with trace.record():
            result = bench_harness.run_cell(args.workload, args.seed,
                                            args.seconds, bool(args.trace))
        result["spans_kept"] = len(trace.drain())
    else:
        result = bench_harness.run_cell(args.workload, args.seed,
                                        args.seconds, bool(args.trace))
    if args.trace:
        result["split"] = split(SplitRun.last, result["metrics"])
        bench_harness.log(json.dumps(result["split"], indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
