"""CPU tests of the readers that read the program's own spans
(``bench_program`` and the ``metrics/`` files on it), on made-up spans of
a made-up run, and of the split of device idle time by the dispatching
thread's spans, on a small made-up trace with two threads and on a real
profiler trace of two threads."""

from __future__ import annotations

import os
import sys
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_harness as H  # noqa: E402
import bench_program as P  # noqa: E402
from bench_trace import Summary  # noqa: E402

from repro.core import trace  # noqa: E402


def made_span(name, t0, t1, request, **attrs):
    sp = trace.Span(name, None, request, attrs)
    sp.t0, sp.t1 = t0, t1
    return sp


def train_rec(program):
    rec = H.RunRecord(cell={}, cfg={}, traffic={"mode": "train"}, peaks={},
                      saves=[{"step": 8}, {"step": 16}])
    rec.trace = Summary(window_s=10.0, busy_s=1.0, module_s={},
                        gaps_by_span={})
    rec.program = program
    return rec


def two_saves():
    out = []
    for step, base in ((8, 0.0), (16, 10.0)):
        out += [made_span("cnr.checkpoint", base, base + 4.0, step),
                made_span("cnr.snapshot.drain", base, base + 1.5, step),
                made_span("cnr.snapshot.copy", base + 1.5, base + 2.0, step,
                          bytes=2_000_000_000),
                made_span("cnr.save.wait", base + 2.0, base + 4.0, step),
                made_span("cnr.save", base + 4.0, base + 8.0, step),
                made_span("cnr.save.commit", base + 7.9, base + 8.0, step)]
        for k in range(3):           # three chunks on two encode workers
            out += [made_span("cnr.save.encode", base + 4 + k, base + 5 + k,
                              step, rows=10, bytes=100),
                    made_span("cnr.save.quant", base + 4 + k, base + 4.5 + k,
                              step, rows=10),
                    made_span("cnr.save.write", base + 5 + k, base + 5.25 + k,
                              step, bytes=100)]
    # a save outside the window's committed saves is left out
    out.append(made_span("cnr.save.encode", 30.0, 99.0, 24))
    return out


@pytest.mark.parametrize("metric,want", [
    ("boundary_drain_s", 1.5),
    ("snapshot_copy_s", 0.5),
    ("snapshot_d2h_gbps", 4.0),
    ("save_wait_s", 2.0),
    ("encode_busy_s", 3.0),
    ("quant_busy_s", 1.5),
    ("write_busy_s", 0.75),
    ("commit_s", 0.1),
])
def test_save_readers(metric, want):
    rec = train_rec(two_saves())
    assert H.load_reader(metric)(rec) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("restore_fetch_busy_s", 3.0),
    ("restore_decode_busy_s", 1.0),
    ("restore_apply_busy_s", 0.5),
    ("restore_h2d_gbps", 3.0),
])
def test_restore_readers(metric, want):
    prog = []
    for req, base in ((1, 0.0), (2, 20.0)):
        prog.append(made_span("cnr.restore", base, base + 9.0, req))
        prog += [made_span("cnr.restore.fetch", base + k, base + k + 1.5,
                           req, rows=5, bytes=10) for k in range(2)]
        prog.append(made_span("cnr.restore.decode", base + 2, base + 3, req))
        prog.append(made_span("cnr.restore.apply", base + 3, base + 3.5, req))
        prog.append(made_span("cnr.restore.place", base + 9.0, base + 10.0,
                              req, bytes=3_000_000_000))
    rec = H.RunRecord(cell={}, cfg={}, traffic={"mode": "resume"}, peaks={},
                      restores=[{}, {}])
    rec.trace = Summary(window_s=30.0, busy_s=0.1, module_s={},
                        gaps_by_span={})
    rec.program = prog
    assert H.load_reader(metric)(rec) == pytest.approx(want)


def test_readers_silent_without_program_spans():
    rec = train_rec([])
    for m in ("boundary_drain_s", "snapshot_d2h_gbps", "encode_busy_s",
              "restore_fetch_busy_s", "restore_h2d_gbps"):
        assert H.load_reader(m)(rec) is None, m


def test_spans_drain_the_program_once_into_the_record():
    trace.drain()
    with trace.record():
        with trace.span("cnr.snapshot.drain", request=8):
            pass
    rec = train_rec(None)
    got = P.spans(rec)
    assert [s.name for s in got] == ["cnr.snapshot.drain"]
    assert P.spans(rec) is got and trace.drain() == []
    untraced = H.RunRecord(cell={}, cfg={}, traffic={}, peaks={})
    assert P.spans(untraced) == []


def test_dispatch_gaps_take_the_dispatching_threads_spans():
    """Thread A holds the window and checkpoints; thread B writes. Idle
    time goes to A's innermost cnr.* span, then A's bench.* span, then
    host.other — never to B's spans."""
    a = [(0.0, 20.0, "bench.window"),
         (1.0, 9.0, "bench.interval"),
         (5.0, 9.0, "bench.checkpoint"),
         (5.0, 9.0, "cnr.checkpoint"),
         (5.0, 6.0, "cnr.snapshot.copy"),
         (6.0, 9.0, "cnr.save.wait"),
         (12.0, 14.0, "bench.place"),
         (11.0, 15.0, "cnr.restore.place")]
    b = [(0.0, 20.0, "bench.put"), (6.0, 9.0, "cnr.save.write")]
    assert P.dispatch_line([b, a]) is a
    gaps = [(0.5, 2.0), (4.0, 7.0), (8.5, 10.0), (12.5, 13.0), (19.0, 20.0)]
    got = P.attribute_dispatch_gaps(gaps, a)
    assert got == pytest.approx({
        "host.other": 0.5 + 1.0 + 1.0,
        "bench.interval": 1.0 + 1.0,
        "cnr.snapshot.copy": 1.0,
        "cnr.save.wait": 1.0 + 0.5,
        "cnr.restore.place": 0.5})
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))
    with pytest.raises(ValueError):
        P.dispatch_line([b])


def test_host_lines_from_a_real_trace(tmp_path):
    import jax

    def writer():
        with trace.span("cnr.save.write"):
            pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace.drain()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with trace.span("cnr.checkpoint", request=3):
                t = threading.Thread(target=writer)
                t.start()
                t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    lines = P.read_host_lines(str(tmp_path))
    names = sorted(n for evs in lines for _, _, n in evs)
    assert names == ["bench.window", "cnr.checkpoint", "cnr.save.write"]
    main = P.dispatch_line(lines)
    assert sorted(n for _, _, n in main) == ["bench.window", "cnr.checkpoint"]
    # the profiler was on: the program kept its spans in memory too
    kept = trace.drain()
    assert sorted(s.name for s in kept) == ["cnr.checkpoint",
                                            "cnr.save.write"]


def test_train_step_time_outside_its_ops():
    """What lies in a train_step execution where no XLA op runs: the other
    lines' events there, clipped to the holes."""
    import trace_split as TS

    dev = {"XLA Modules": [(0.0, 10.0, "jit_train_step(7)"),
                           (11.0, 12.0, "jit_quant_pack_pallas(2)")],
           "XLA Ops": [(0.0, 4.0, "fusion"), (6.0, 9.0, "scatter"),
                       (11.0, 12.0, "kernel")],
           "Steps": [(3.0, 7.0, "step 1")],
           "Host Offload": [(9.5, 11.5, "copy")]}
    got = TS.train_step_gaps([dev], 0.0, 12.0)
    assert got["module_s"] == pytest.approx(10.0)
    assert got["outside_ops_s"] == pytest.approx(3.0)
    assert got["other_lines"] == {"Steps": [["step 1", pytest.approx(2.0)]],
                                  "Host Offload": [["copy",
                                                    pytest.approx(0.5)]]}
    assert TS.subtract([(0, 10), (12, 20)], [(1, 2), (9, 13), (15, 16)]) \
        == [(0, 1), (2, 9), (13, 15), (16, 20)]
