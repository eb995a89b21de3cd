"""Property tests for the commit-time delta index (docs/serving.md).

Two invariants every serving consumer relies on:

* **superset** — the index's claimed touched-row spans always cover every
  row whose bytes actually changed between consecutive steps, under
  arbitrary save/GC interleavings (span compression widens, never
  narrows);
* **cost** — catch-up bytes computed from the index alone match the range
  planner's own estimate for replaying the same suffix.

Hypothesis drives randomized versions when installed; CI stubs it
(conftest), so each property also has pinned deterministic examples that
always run.
"""

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import CheckNRunManager, CheckpointConfig, InMemoryStore
from repro.core import manifest as mf
from repro.core import range_reader as rr
from repro.core.snapshot import Snapshot
from repro.serve.delta_index import (
    MAX_CHUNK_SPANS,
    MAX_SPANS,
    catchup_cost,
    compress_spans,
    delta_of,
    merge_spans,
    touched_union,
)


def spans_cover(spans, rows):
    """True iff every row index in ``rows`` falls inside some span."""
    return all(any(lo <= r < hi for lo, hi in spans) for r in rows)


def span_rows(spans):
    return sum(hi - lo for lo, hi in spans)


# --------------------------------------------------------- compress_spans
def test_compress_spans_exact_runs():
    idx = np.array([0, 1, 2, 7, 8, 20])
    assert compress_spans(idx) == [[0, 3], [7, 9], [20, 21]]


def test_compress_spans_empty_and_single():
    assert compress_spans(np.array([], dtype=np.int64)) == []
    assert compress_spans(np.array([5])) == [[5, 6]]


def test_compress_spans_cap_merges_smallest_gaps():
    # runs at 0, 10, 11, 100 — cap 2 must keep the widest gap (11→100)
    idx = np.array([0, 10, 11, 100])
    assert compress_spans(idx, cap=2) == [[0, 12], [100, 101]]


def test_compress_spans_cap_is_superset_and_deterministic():
    rng = np.random.default_rng(7)
    for _ in range(25):
        idx = np.unique(rng.integers(0, 5000, size=rng.integers(1, 400)))
        spans = compress_spans(idx, cap=8)
        assert spans == compress_spans(idx, cap=8)  # deterministic
        assert len(spans) <= 8
        assert spans_cover(spans, idx)
        # sorted + disjoint
        for a, b in zip(spans, spans[1:]):
            assert a[1] < b[0]
        # JSON-safe plain ints (np.int64 would break manifest dumps)
        assert all(type(v) is int for s in spans for v in s)


@given(st.lists(st.integers(min_value=0, max_value=2000),
                min_size=1, max_size=300),
       st.integers(min_value=1, max_value=32))
@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_compress_spans_superset_property(rows, cap):
    idx = np.unique(np.asarray(rows))
    spans = compress_spans(idx, cap=cap)
    assert len(spans) <= cap
    assert spans_cover(spans, idx)


def _compress_spans_reference(idx, cap=MAX_CHUNK_SPANS):
    """``compress_spans`` as it was before it became array code: one Python
    span per run, capped by :func:`_cap_spans_reference`. The oracle the
    array version must match exactly, ties included."""
    n = len(idx)
    if n == 0:
        return []
    breaks = np.flatnonzero(np.diff(idx) > 1)
    starts = np.concatenate(([0], breaks + 1))
    ends = np.concatenate((breaks, [n - 1]))
    spans = [[int(idx[s]), int(idx[e]) + 1] for s, e in zip(starts, ends)]
    return _cap_spans_reference(spans, cap)


def _cap_spans_reference(spans, cap):
    if cap <= 0 or len(spans) <= cap:
        return spans
    gaps = sorted(((spans[i + 1][0] - spans[i][1], i)
                   for i in range(len(spans) - 1)), reverse=True)
    keep = sorted(i for _, i in gaps[:cap - 1])
    out = []
    lo = spans[0][0]
    prev_end = spans[0][1]
    j = 0
    for i in range(len(spans) - 1):
        if j < len(keep) and keep[j] == i:
            out.append([lo, prev_end])
            lo = spans[i + 1][0]
            j += 1
        prev_end = spans[i + 1][1]
    out.append([lo, prev_end])
    return out


def _log_uniform_chunk(chunk, rows=2_000_384, samples=8 * 65_536):
    """One 65,536-row chunk of an interval's sorted unique ids, drawn
    log-uniformly over a ``rows``-row table as the DLRM benchmark cell
    draws them (chunk 0 is the dense head, chunk 1 the sparse tail)."""
    u = np.random.default_rng(14).random(samples) * np.log(rows)
    ids = np.unique(np.minimum(np.exp(u).astype(np.int64) - 1, rows - 1))
    return ids[chunk * 65_536:(chunk + 1) * 65_536]


def _runs(idx):
    return int(len(idx) and 1 + np.count_nonzero(np.diff(
        np.asarray(idx, np.int64)) > 1))


EQUIV_IDS = {
    "empty": np.array([], np.int64),
    "one_row": np.array([7]),
    "one_run": np.arange(100, 140),
    "random": np.unique(np.random.default_rng(3).integers(0, 5000, 600)),
    # every gap equal: the cap keeps the last ones
    "equal_gaps": np.arange(0, 600, 3),
    # equal gaps at several positions, around and between wider ones
    "tied_gaps": np.cumsum(np.tile([1, 6, 3, 6, 1, 9, 6, 3, 9, 1], 12)),
    "log_uniform_head": _log_uniform_chunk(0),
    "log_uniform_tail": _log_uniform_chunk(1),
}


@pytest.mark.parametrize("dtype", [np.uint32, np.int64])
@pytest.mark.parametrize("cap", [0, 1, 2, 16, 64, "runs-1", "runs", 10**6])
@pytest.mark.parametrize("case", sorted(EQUIV_IDS))
def test_compress_spans_matches_reference(case, cap, dtype):
    idx = EQUIV_IDS[case].astype(dtype)
    if isinstance(cap, str):
        cap = _runs(idx) - (cap == "runs-1")
    got = compress_spans(idx, cap=cap)
    assert got == _compress_spans_reference(idx, cap)
    assert all(type(v) is int for s in got for v in s)


def test_compress_spans_equal_gaps_keep_the_later():
    # runs at 0, 3, 6, 9 with three gaps of 2: cap 3 keeps the last two
    idx = np.array([0, 3, 6, 9])
    assert compress_spans(idx, cap=3) == [[0, 4], [6, 7], [9, 10]]


@given(st.lists(st.integers(min_value=0, max_value=3000), max_size=400),
       st.integers(min_value=0, max_value=40),
       st.sampled_from([np.uint32, np.int64]))
@settings(max_examples=200, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_compress_spans_matches_reference_property(rows, cap, dtype):
    idx = np.unique(np.asarray(rows, dtype=np.int64)).astype(dtype)
    got = compress_spans(idx, cap=cap)
    assert got == _compress_spans_reference(idx, cap)
    assert all(type(v) is int for s in got for v in s)


def test_merge_spans_union_and_cap():
    assert merge_spans([[5, 7], [0, 3], [2, 4], [7, 9]]) == [[0, 4], [5, 9]]
    assert merge_spans([[3, 3], [9, 4]]) == []  # empty/inverted drop
    many = [[10 * i, 10 * i + 1] for i in range(MAX_SPANS + 40)]
    capped = merge_spans(many)
    assert len(capped) <= MAX_SPANS
    assert spans_cover(capped, [s[0] for s in many])


# ---------------------------------------------------- index vs real saves
def drive(policy, touch_plan, rows=220, dim=4, seed=3, gc_keep=None):
    """Save a chain with the given per-step touched fractions; return
    (store, per-step dict of table arrays). ``gc_keep`` applies retention
    after the last save."""
    rng = np.random.default_rng(seed)
    tabs = {"emb0": rng.normal(size=(rows, dim)).astype(np.float32),
            "emb1": rng.normal(size=(rows + 37, dim)).astype(np.float32)}
    store = InMemoryStore()
    mgr = CheckNRunManager(store, CheckpointConfig(
        policy=policy, quant=None, async_write=False, chunk_rows=64,
        keep_latest=10))
    states = {}
    try:
        for step, frac in enumerate(touch_plan, start=1):
            touched = {}
            for name, arr in tabs.items():
                n = max(1, int(arr.shape[0] * frac))
                idx = rng.choice(arr.shape[0], size=n, replace=False)
                arr[idx] += rng.normal(size=(n, dim)).astype(np.float32)
                t = np.zeros(arr.shape[0], bool)
                t[idx] = True
                touched[name] = t
            mgr.save(Snapshot(
                step=step,
                tables={k: v.copy() for k, v in tabs.items()},
                row_state={n: {} for n in tabs}, touched=touched,
                dense={"w": rng.normal(size=(8,)).astype(np.float32)},
                extra={}), block=True)
            states[step] = {k: v.copy() for k, v in tabs.items()}
        if gc_keep is not None:
            mf.apply_retention(store, keep_latest=gc_keep)
    finally:
        mgr.close()
    return store, states


def assert_superset_and_cost(store, states):
    """Core property pair for every committed step of a driven chain."""
    steps = mf.list_steps(store)
    for step in steps:
        man = mf.load(store, step)
        d = delta_of(man)
        prev = step - 1
        if prev in states:
            for name, arr in states[step].items():
                changed = np.flatnonzero(
                    (arr != states[prev][name]).any(axis=1))
                spans = d["tables"][name]["spans"]
                assert spans_cover(spans, changed), (
                    f"step {step} table {name}: changed rows escape the "
                    f"claimed spans")
        # cost: index-only estimate == range planner's estimate
        chain = mf.recovery_chain(store, step)
        for start in range(len(chain)):
            suffix = chain[start:]
            est = catchup_cost(suffix)
            plan = rr.plan_ranges(suffix)
            assert est["nbytes"] == plan.nbytes, (
                f"step {step} suffix {[m.step for m in suffix]}")
            assert est["chunk_bytes"] == plan.chunk_bytes
            assert est["dense_bytes"] == plan.dense_bytes


@pytest.mark.parametrize("policy", ["consecutive", "intermittent",
                                    "one_shot"])
def test_index_superset_and_cost_pinned(policy):
    store, states = drive(policy, [1.0, 0.05, 0.1, 0.02, 0.3, 0.05])
    assert_superset_and_cost(store, states)


def test_index_superset_and_cost_after_gc():
    # retention drops early steps; surviving manifests must still satisfy
    # both properties (cumulative chains lose intermediates by design)
    store, states = drive("intermittent", [1.0, 0.04, 0.04, 0.04, 0.04],
                          gc_keep=2)
    steps = mf.list_steps(store)
    assert len(steps) >= 2
    assert_superset_and_cost(store, states)


def test_version0_derivation_matches_for_legacy_manifests():
    """Strip the stamped index (simulating a pre-PR manifest): delta_of
    must derive a version-0 record that still superset-covers and still
    costs catch-up exactly like the planner (coarser spans are fine)."""
    store, states = drive("consecutive", [1.0, 0.05, 0.1])
    for step in mf.list_steps(store):
        man = mf.load(store, step)
        stamped = delta_of(man)
        man.delta = None
        for rec in man.tables.values():
            for ch in rec.chunks:
                ch.row_spans = None
        derived = delta_of(man)
        assert derived["version"] == 0
        assert stamped["version"] == 1
        for name, t in stamped["tables"].items():
            dt = derived["tables"][name]
            # byte/row totals are chunk-record sums — identical
            assert dt["payload_bytes"] == t["payload_bytes"]
            assert dt["rows_touched"] == t["rows_touched"]
            # derived spans are coarser but must cover the stamped ones
            assert span_rows(dt["spans"]) >= span_rows(t["spans"])
            assert spans_cover(dt["spans"],
                               [lo for lo, _ in t["spans"]]
                               + [hi - 1 for _, hi in t["spans"]])
        assert derived["dense_bytes"] == stamped["dense_bytes"]


def test_touched_union_covers_all_suffix_changes():
    store, states = drive("consecutive", [1.0, 0.05, 0.05, 0.05])
    chain = mf.recovery_chain(store, 4)
    suffix = [m for m in chain if m.step > 1]
    union = touched_union(suffix)
    for name in states[4]:
        changed = np.flatnonzero(
            (states[4][name] != states[1][name]).any(axis=1))
        assert spans_cover(union[name], changed)


def test_incremental_chunk_records_carry_capped_spans():
    store, _ = drive("consecutive", [1.0, 0.3])
    man = mf.load(store, 2)
    assert man.kind == "incremental"
    for rec in man.tables.values():
        for ch in rec.chunks:
            assert ch.row_spans is not None
            assert 1 <= len(ch.row_spans) <= MAX_CHUNK_SPANS
            assert sum(hi - lo for lo, hi in ch.row_spans) >= ch.n_rows
    # full chunks stay range-encoded, no redundant spans
    full = mf.load(store, 1)
    for rec in full.tables.values():
        for ch in rec.chunks:
            assert ch.row_spans is None and ch.row_range is not None


@given(st.lists(st.floats(min_value=0.01, max_value=0.5),
                min_size=2, max_size=6),
       st.integers(min_value=0, max_value=10_000),
       st.sampled_from(["consecutive", "intermittent", "one_shot"]),
       st.one_of(st.none(), st.integers(min_value=1, max_value=3)))
@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_index_properties_random_interleavings(fracs, seed, policy,
                                               gc_keep):
    store, states = drive(policy, [1.0] + fracs, seed=seed,
                          gc_keep=gc_keep)
    assert_superset_and_cost(store, states)
