"""Program spans (repro.core.trace): parents and request ids across pool
threads, nothing kept with recording off, a bounded buffer, compiles
counted under the span that compiled; and the span tree of a tiny
Trainer save + restore, whose byte attributes must add up to what the
manifest, the snapshot and the restore report."""

import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_cell
from repro.core import (CheckNRunManager, CheckpointConfig, InMemoryStore,
                        PAPER_DEFAULTS)
from repro.core import manifest as mf
from repro.core import trace
from repro.core.metrics import render_prometheus
from repro.core.pipeline import RestorePipeline, WritePipeline
from repro.core.snapshot import Snapshot
from repro.train.loop import Trainer, TrainerConfig


@pytest.fixture(autouse=True)
def empty_buffer():
    trace.drain()
    yield
    trace.drain()


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_nothing_kept_with_recording_off():
    with trace.span("cnr.test.off", rows=3) as sp:
        pass
    assert sp.seconds >= 0.0
    assert trace.drain() == []


def test_record_keeps_name_times_thread_and_attrs():
    with trace.record():
        with trace.span("cnr.test.outer", request=7, rows=2) as outer:
            with trace.span("cnr.test.inner") as inner:
                trace.annotate(bytes=11)
    got = trace.drain()
    assert [s.name for s in got] == ["cnr.test.inner", "cnr.test.outer"]
    assert inner.parent_id == outer.id and outer.parent_id is None
    assert inner.request == outer.request == 7
    assert inner.attrs == {"bytes": 11} and outer.attrs == {"rows": 2}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert inner.thread == threading.current_thread().name


def test_set_request_after_open_and_new_requests_differ():
    with trace.record():
        with trace.span("cnr.test.late") as sp:
            sp.set(request=42, step=42)
    (got,) = trace.drain()
    assert got.request == 42 and got.attrs == {"step": 42}
    assert trace.new_request() != trace.new_request()


def test_write_pipeline_spans_join_the_submitting_span():
    """Stage spans run on pool threads under the span that submitted the
    item, share its request id, and carry the item's attributes and the
    payload's bytes; busy seconds are the spans' own."""
    with trace.record():
        with trace.span("cnr.test.save", request=5) as root:
            pipe = WritePipeline(encode_workers=3, write_workers=2)
            for i in range(6):
                pipe.submit(lambda i=i: (b"x" * (i + 1), i), lambda p: None,
                            attrs=dict(rows=10 * i))
            pipe.drain()
            pipe.close()
    spans = trace.drain()
    enc, wr = by_name(spans, "cnr.save.encode"), by_name(spans, "cnr.save.write")
    assert len(enc) == len(wr) == 6
    for s in enc + wr:
        assert s.parent_id == root.id and s.request == 5
        assert s.thread != threading.current_thread().name
    assert sorted(s.attrs["rows"] for s in enc) == [0, 10, 20, 30, 40, 50]
    assert sorted(s.attrs["bytes"] for s in wr) == [1, 2, 3, 4, 5, 6]
    assert pipe.stats.busy["encode"] == pytest.approx(
        sum(s.seconds for s in enc))


def test_restore_pipeline_span_prefix():
    with trace.record():
        pipe = RestorePipeline(fetch_workers=2, decode_workers=2,
                               span_prefix="cnr.refresh")
        for i in range(3):
            pipe.submit(lambda i=i: b"y" * i, lambda d: d, lambda d: len(d))
        assert pipe.drain() == [0, 1, 2]
        pipe.close()
    names = {s.name for s in trace.drain()}
    assert names == {"cnr.refresh.fetch", "cnr.refresh.decode",
                     "cnr.refresh.apply"}


def test_context_carries_parent_into_a_thread_pool():
    import contextvars

    with trace.record():
        with trace.span("cnr.test.root", request=9) as root:
            with ThreadPoolExecutor(2) as pool:
                ctx = contextvars.copy_context()
                pool.submit(ctx.run, lambda: trace.span("cnr.test.child")
                            .__enter__().__exit__(None, None, None)).result()
    (child,) = by_name(trace.drain(), "cnr.test.child")
    assert child.parent_id == root.id and child.request == 9


def test_buffer_is_bounded(monkeypatch):
    monkeypatch.setattr(trace._recorder, "spans",
                        trace.collections.deque(maxlen=8))
    before = trace.dropped()
    with trace.record():
        for i in range(20):
            with trace.span("cnr.test.many", request=i):
                pass
    got = trace.drain()
    assert [s.request for s in got] == list(range(12, 20))
    assert trace.dropped() - before == 12


def test_compile_counted_under_the_innermost_span_and_its_parents():
    @jax.jit
    def f(x):
        return x * 3 + 1

    with trace.span("cnr.test.outer") as outer:
        with trace.span("cnr.test.compile") as inner:
            f(jnp.arange(17, dtype=jnp.float32)).block_until_ready()
        with trace.span("cnr.test.cached") as cached:
            f(jnp.arange(17, dtype=jnp.float32)).block_until_ready()
    assert inner.compiles >= 1 and cached.compiles == 0
    assert outer.compiles == inner.compiles


@pytest.mark.parametrize("async_write", [False, True])
def test_row_spans_span_per_incremental_chunk(async_write):
    """Each incremental chunk opens one ``cnr.save.row_spans`` under its
    encode span, with the chunk's rows; a full save opens none."""
    rng = np.random.default_rng(5)
    tab = rng.normal(size=(300, 4)).astype(np.float32)
    store = InMemoryStore()
    mgr = CheckNRunManager(store, CheckpointConfig(
        policy="consecutive", quant=PAPER_DEFAULTS[4],
        async_write=async_write, chunk_rows=64))
    try:
        with trace.record():
            for step in (1, 2):
                tab[rng.random(300) < 0.5] += 1.0
                mgr.save(Snapshot(
                    step=step, tables={"emb": tab.copy()},
                    row_state={"emb": {}},
                    touched={"emb": rng.random(300) < 0.5},
                    dense={"w": np.zeros(8, np.float32)}, extra={}),
                    block=True)
    finally:
        mgr.close()
    spans = trace.drain()
    full, incr = mf.load(store, 1), mf.load(store, 2)
    assert (full.kind, incr.kind) == ("full", "incremental")
    assert not [s for s in spans if s.request == 1
                and s.name == "cnr.save.row_spans"]
    of = [s for s in spans if s.request == 2]
    rs = by_name(of, "cnr.save.row_spans")
    chunks = [c for c in incr.tables["emb"].chunks if c.n_rows]
    assert len(rs) == len(chunks) >= 2
    assert sorted(s.attrs["rows"] for s in rs) == sorted(
        c.n_rows for c in chunks)
    encode = {s.id for s in by_name(of, "cnr.save.encode")}
    assert all(s.parent_id in encode for s in rs)


# ------------------------------------------------------- a tiny Trainer


@pytest.fixture(scope="module")
def bundle():
    return get_cell("dlrm-rm2", "train_batch", reduced=True)


def test_trainer_save_and_restore_span_tree(bundle):
    store = InMemoryStore()
    ckpt = CheckpointConfig(interval_batches=2, policy="consecutive",
                            quant=PAPER_DEFAULTS[4], async_write=True,
                            chunk_rows=64)
    tr = Trainer(bundle, store, ckpt, TrainerConfig(log_every=1 << 30))
    tr.init_or_restore()
    with trace.record():
        tr.run(4)
        tr.manager.wait()
    saves = trace.drain()
    snaps = {s: tr._boundary_snaps[s] for s in (2, 4)}
    metrics = tr.manager.metrics()
    tr.close()

    ckpts = by_name(saves, "cnr.checkpoint")
    assert [s.request for s in ckpts] == [2, 4]
    assert tr.stall_times == [s.seconds for s in ckpts]
    for step, snap in snaps.items():
        of = [s for s in saves if s.request == step]
        root = next(s for s in of if s.name == "cnr.save")
        man = mf.load(store, step)
        assert root.attrs["kind"] == man.kind
        assert root.attrs["bytes"] == man.nbytes_total
        n_chunks = sum(len(r.chunks) for r in man.tables.values())
        enc = by_name(of, "cnr.save.encode")
        assert len(enc) == n_chunks + len(man.dense)
        assert all(s.parent_id == root.id for s in enc)
        assert sum(s.attrs["bytes"] for s in by_name(of, "cnr.save.write")) \
            == man.nbytes_total
        quant = by_name(of, "cnr.save.quant")
        assert len(quant) == n_chunks
        assert sum(s.attrs["rows"] for s in quant) == sum(
            c.n_rows for r in man.tables.values() for c in r.chunks)
        (copy,) = by_name(of, "cnr.snapshot.copy")
        assert copy.attrs["bytes"] == snap.total_param_bytes() + sum(
            t.nbytes for t in snap.touched.values())
        ckpt_span = next(s for s in of if s.name == "cnr.checkpoint")
        for name in ("cnr.snapshot.drain", "cnr.snapshot.copy",
                     "cnr.snapshot.release", "cnr.save.wait"):
            (s,) = by_name(of, name)
            assert s.parent_id == ckpt_span.id
        assert len(by_name(of, "cnr.save.commit")) == 1
    assert metrics.snapshot_bytes_total == sum(
        s.copied_bytes() for s in snaps.values())
    text = render_prometheus(metrics.to_dict())
    assert "# HELP cnr_snapshot_bytes_total " in text
    assert "# HELP cnr_compiles_total " in text

    t2 = Trainer(bundle, store, ckpt, TrainerConfig(log_every=1 << 30))
    with trace.record():
        assert t2.init_or_restore() == 4
    spans = trace.drain()
    (root,) = by_name(spans, "cnr.restore")
    rs_bytes = t2.manager.metrics().restore_bytes_total
    t2.close()
    assert root.attrs["chain_len"] == 2
    fetch = by_name(spans, "cnr.restore.fetch")
    assert fetch and all(s.request == root.request for s in fetch)
    assert sum(s.attrs["bytes"] for s in fetch) == rs_bytes
    assert len(by_name(spans, "cnr.restore.decode")) == len(fetch)
    assert len(by_name(spans, "cnr.restore.apply")) == len(fetch)
    (place,) = by_name(spans, "cnr.restore.place")
    assert place.request == root.request
    leaves = jax.tree.leaves((t2.state.params, t2.state.opt_state))
    assert place.attrs["bytes"] >= sum(np.asarray(x).nbytes for x in leaves)
