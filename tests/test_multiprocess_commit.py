"""Acceptance suite for coordinator-less commit over REAL multi-process
hosts (docs/sharded_writers.md).

The contract: each host runs as its own OS process over a shared
``LocalFSStore``; after voting, each host polls the parts namespace and the
last host to observe all votes commits the global manifest itself — there
is no coordinator rank. SIGKILLing any host process at any protocol point
(mid-chunks, just before its vote, just after its vote, mid-phase-2-merge)
never loses the previous committed step: restore returns it
byte-identically. Two hosts racing phase 2 produce exactly one global
manifest whose bytes are identical regardless of which host won. A
completed multiprocess save restores byte-identically to the
thread-simulated and single-host paths.

Host processes are driven two ways: through
``CheckNRunManager(multiprocess=True)`` for the happy path, and directly
via ``repro.dist.host_proc`` (spill + Popen) where a ``--fault`` flag must
SIGKILL the process at an exact protocol point.

The heavy cases (4 host processes each paying a cold jax import, and the
no-commit matrix rows that wait out the quorum timeout) are ``slow``-marked
for the nightly job; the push-time fast set keeps the 2-process racing-
committer canary plus the in-process protocol tests, and CI separately
gates every push on a real 2-process save via
``benchmarks/write_path.py --tiny --multiprocess-only``.
"""

import dataclasses
import os
import subprocess

import numpy as np
import pytest

from repro.core import (
    CheckNRunManager,
    CheckpointConfig,
    CommitContext,
    InMemoryStore,
    LocalFSStore,
)
from repro.core import manifest as mf
from repro.dist import host_proc
from tests.fault_injection import assert_no_torn_manifests

NUM_HOSTS = 4
# quorum-wait for hosts whose peers died pre-vote: long enough for a host
# to import jax, write its tiny shard, and poll; short enough to keep the
# no-commit matrix cases fast
COMMIT_TIMEOUT_S = 6.0


def make_cfg(**overrides):
    cfg = dict(policy="full_only", quant=None, async_write=False,
               chunk_rows=64, keep_latest=10, num_hosts=NUM_HOSTS,
               commit_timeout_s=30.0)
    cfg.update(overrides)
    return CheckpointConfig(**cfg)


def capture(rs):
    return ({n: t.copy() for n, t in rs.tables.items()},
            {n: {a: v.copy() for a, v in d.items()}
             for n, d in rs.row_state.items()},
            {n: v.copy() for n, v in rs.dense.items()})


def assert_state_equal(rs, ref):
    tables, row_state, dense = ref
    assert set(rs.tables) == set(tables)
    for n in tables:
        np.testing.assert_array_equal(rs.tables[n], tables[n])
        for a in row_state[n]:
            np.testing.assert_array_equal(rs.row_state[n][a], row_state[n][a])
    assert set(rs.dense) == set(dense)
    for n in dense:
        np.testing.assert_array_equal(rs.dense[n], dense[n])


def touch(snap, rng, k=40):
    for name, tab in snap.tables.items():
        idx = rng.choice(tab.shape[0], size=k, replace=False)
        tab[idx] += rng.normal(size=(k, tab.shape[1])).astype(np.float32)
    return snap


def orchestrate(store_root, tmp_path, snap, step, *, faults=None,
                race_commit=False, race_hosts=None, dump_manifests=False,
                num_hosts=NUM_HOSTS, commit_timeout=COMMIT_TIMEOUT_S):
    """Spill ``snap`` and run one real host process per host, with optional
    per-host ``--fault`` SIGKILL points. ``race_commit`` (all hosts) or
    ``race_hosts`` (a subset) force the committer path — the host skips the
    manifest-exists fast path, so its own commit attempt is guaranteed.
    Returns (exit codes, dump paths)."""
    cfg = make_cfg(num_hosts=num_hosts, multiprocess=True)
    ctx = CommitContext(kind="full", base_step=step, prev_step=None,
                        quant=None, policy={"name": "full_only"},
                        extra={"bitwidth": None})
    spill = str(tmp_path / f"spill_{step}")
    host_proc.write_spill(spill, snap, {}, {}, cfg, step, num_hosts, ctx,
                          verify_chunks=True)
    env = host_proc.child_env()
    procs, dumps = [], []
    for h in range(num_hosts):
        dump = str(tmp_path / f"would_commit_{h}.json")
        dumps.append(dump)
        cmd = host_proc.host_command(
            store_root, spill, h,
            fault=(faults or {}).get(h),
            race_commit=race_commit or h in (race_hosts or ()),
            dump_manifest=dump if dump_manifests else None,
            poll_interval_s=0.02, commit_timeout_s=commit_timeout)
        log = open(str(tmp_path / f"host_{h}.log"), "wb")
        procs.append((subprocess.Popen(cmd, env=env, stdout=log,
                                       stderr=subprocess.STDOUT), log))
    codes = []
    for p, log in procs:
        codes.append(p.wait(timeout=120))
        log.close()
    return codes, dumps


def committed_step1(tmp_path, tiny_snapshot):
    """A committed 4-host step-1 checkpoint on a LocalFSStore, its restored
    state, and the snapshot used — shared setup for the crash matrix."""
    root = str(tmp_path / "store")
    store = LocalFSStore(root)
    mgr = CheckNRunManager(store, make_cfg())
    snap = tiny_snapshot(step=1)
    mgr.save(snap).result()
    ref = capture(mgr.restore())
    mgr.close()
    return root, store, snap, ref


# --------------------------------------------------------------------------
# byte-identity: multiprocess ≡ thread-simulated ≡ single-host
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_multiprocess_restores_byte_identical_to_thread_and_single(
        tmp_path, tiny_snapshot):
    snap = tiny_snapshot(step=1, tables=3)

    s1 = InMemoryStore()
    m1 = CheckNRunManager(s1, make_cfg(num_hosts=1))
    m1.save(snap).result()
    ref = capture(m1.restore())
    m1.close()

    st = InMemoryStore()
    mt = CheckNRunManager(st, make_cfg())
    mt.save(snap).result()

    root = str(tmp_path / "store")
    sp = LocalFSStore(root)
    mp = CheckNRunManager(sp, make_cfg(multiprocess=True,
                                       spill_dir=str(tmp_path)))
    res = mp.save(snap).result()
    assert res.pipeline_stats["multiprocess"] is True
    assert res.pipeline_stats["exit_codes"] == [0] * NUM_HOSTS

    # restored state: all three paths byte-identical
    assert_state_equal(mt.restore(), ref)
    assert_state_equal(mp.restore(), ref)

    # the blob layer itself is byte-identical between thread-simulated and
    # real-process hosts: same chunk keys, same payload bytes
    t_chunks = {k: st.get(k) for k in st.list("chunks/")}
    p_chunks = {k: sp.get(k) for k in sp.list("chunks/")}
    assert t_chunks == p_chunks
    man = mf.load(sp, 1)
    assert man.shards["num_hosts"] == NUM_HOSTS
    assert_no_torn_manifests(sp)
    mt.close()
    mp.close()


def test_multiprocess_requires_localfs_store(tiny_snapshot):
    mgr = CheckNRunManager(InMemoryStore(),
                           make_cfg(num_hosts=2, multiprocess=True))
    with pytest.raises(ValueError, match="LocalFSStore"):
        mgr.save(tiny_snapshot(step=1)).result()
    mgr.close()


# --------------------------------------------------------------------------
# crash matrix: SIGKILL any host process at any protocol point
# --------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("fault,may_commit", [
    ("mid_chunks:0", False),   # first chunk put never lands
    ("mid_chunks:2", False),   # dies partway through its shard
    ("before_vote", False),    # chunks durable, killed at the vote put
    ("after_vote", True),      # vote durable → peers form the quorum
    ("mid_merge", True),       # killed at the manifest put → a peer commits
])
def test_sigkilled_host_never_loses_previous_step(tmp_path, tiny_snapshot,
                                                  fault, may_commit):
    root, store, snap, ref = committed_step1(tmp_path, tiny_snapshot)
    victim = 2
    snap2 = dataclasses.replace(touch(snap, np.random.default_rng(3)), step=2)
    # mid_merge: pin the victim to the committer path (--race-commit), or a
    # faster peer may commit first and the victim exits via the observed
    # fast path without ever reaching its own manifest put
    codes, _ = orchestrate(root, tmp_path, snap2, 2,
                           faults={victim: fault},
                           race_hosts={victim} if fault == "mid_merge"
                           else None)

    assert codes[victim] == -9, f"victim exited {codes[victim]}, not SIGKILL"
    assert_no_torn_manifests(store)
    committed = store.exists(mf.manifest_key(2))
    if not may_commit:
        # quorum never formed: peers time out (exit 3), nothing commits
        assert not committed
        assert mf.latest_step(store) == 1
        assert all(c == 3 for h, c in enumerate(codes) if h != victim), codes
    else:
        # the victim's vote was durable, so surviving pollers finish
        # phase 2 — the new step commits completely...
        assert committed
        assert mf.latest_step(store) == 2
        for name, tab in snap2.tables.items():
            np.testing.assert_array_equal(
                CheckNRunManager(store, make_cfg()).restore().tables[name],
                tab)
    # ...and in EVERY case the previous committed step restores
    # byte-identically (retention was not run here — step 1 remains)
    rs = CheckNRunManager(store, make_cfg()).restore(step=1)
    assert_state_equal(rs, ref)


@pytest.mark.slow
def test_all_committers_sigkilled_mid_merge_previous_step_survives(
        tmp_path, tiny_snapshot):
    """The torn-est state: EVERY host (so in particular the true last
    voter) dies exactly at the manifest put — all votes durable, all
    chunks durable, but the commit point never lands. The previous step
    must restore byte-identically, and an operator can later finish
    phase 2 from the durable votes (launch/ckpt commit)."""
    root, store, snap, ref = committed_step1(tmp_path, tiny_snapshot)
    snap2 = dataclasses.replace(touch(snap, np.random.default_rng(5)), step=2)
    codes, _ = orchestrate(root, tmp_path, snap2, 2,
                           faults={h: "mid_merge" for h in range(NUM_HOSTS)})
    assert codes == [-9] * NUM_HOSTS
    assert not store.exists(mf.manifest_key(2))
    assert mf.list_part_hosts(store, 2) == list(range(NUM_HOSTS))
    assert mf.latest_step(store) == 1
    assert_state_equal(CheckNRunManager(store, make_cfg()).restore(), ref)
    assert_no_torn_manifests(store)

    # operational recovery, coordinator-less: ANY process may finish
    # phase 2 idempotently from the durable votes
    from repro.launch.ckpt import main as ckpt_main
    assert ckpt_main(["commit", "--dir", root, "--step", "2",
                      "--num-hosts", str(NUM_HOSTS)]) == 0
    assert mf.latest_step(store) == 2
    for name, tab in snap2.tables.items():
        np.testing.assert_array_equal(
            CheckNRunManager(store, make_cfg()).restore().tables[name], tab)
    assert_no_torn_manifests(store)


# --------------------------------------------------------------------------
# phase-2 race: two hosts both commit; exactly one manifest, identical bytes
# --------------------------------------------------------------------------


def test_racing_phase2_commits_are_byte_identical(tmp_path, tiny_snapshot):
    root = str(tmp_path / "store")
    store = LocalFSStore(root)
    snap = tiny_snapshot(step=1)
    codes, dumps = orchestrate(root, tmp_path, snap, 1, num_hosts=2,
                               race_commit=True, dump_manifests=True)
    assert codes == [0, 0]
    # both hosts took the committer path; the manifests they built (dumped
    # just before their commit_once) are byte-identical — which is exactly
    # why the race is harmless
    blobs = [open(d, "rb").read() for d in dumps]
    assert blobs[0] == blobs[1] and len(blobs[0]) > 0
    assert store.get(mf.manifest_key(1)) == blobs[0]
    assert_no_torn_manifests(store)
    rs = CheckNRunManager(store, make_cfg(num_hosts=2)).restore()
    for name, tab in snap.tables.items():
        np.testing.assert_array_equal(rs.tables[name], tab)


def test_ckpt_commit_refuses_incremental_votes(tmp_path, tiny_snapshot):
    """The operator recovery tool stamps kind="full"; committing an
    INCREMENTAL save's votes that way would zero every untouched row on
    restore — it must detect index-encoded chunks and refuse."""
    from repro.launch.ckpt import main as ckpt_main

    root = str(tmp_path / "store")
    store = LocalFSStore(root)
    mgr = CheckNRunManager(store, make_cfg(policy="one_shot"))
    snap = tiny_snapshot(step=1)
    mgr.save(snap).result()                      # full baseline
    snap2 = dataclasses.replace(touch(snap, np.random.default_rng(1)), step=2)
    mgr.save(snap2).result()                     # incremental
    assert mf.load(store, 2).kind == "incremental"
    # simulate "all committers died mid-merge" for the incremental step
    store.delete(mf.manifest_key(2))
    assert ckpt_main(["commit", "--dir", root, "--step", "2",
                      "--num-hosts", str(NUM_HOSTS)]) == 1
    assert not store.exists(mf.manifest_key(2))
    mgr.close()


def test_try_commit_is_idempotent_in_process(tiny_snapshot):
    """try_commit called repeatedly (as racing last voters would) returns
    the same committed manifest and never rewrites different bytes."""
    from repro.core import try_commit

    store = InMemoryStore()
    mgr = CheckNRunManager(store, make_cfg())
    mgr.save(tiny_snapshot(step=1)).result()
    raw = store.get(mf.manifest_key(1))
    ctx = CommitContext(kind="full", base_step=1, prev_step=None, quant=None,
                        policy=mf.load(store, 1).policy,
                        extra=mf.load(store, 1).extra)
    man = try_commit(store, 1, NUM_HOSTS, ctx)
    assert man.step == 1
    assert store.get(mf.manifest_key(1)) == raw
    mgr.close()


def test_commit_once_rejects_divergent_manifest(tiny_snapshot):
    from repro.core import CommitRaceError, commit_once

    store = InMemoryStore()
    mgr = CheckNRunManager(store, make_cfg())
    mgr.save(tiny_snapshot(step=1)).result()
    man = mf.load(store, 1)
    assert commit_once(store, man) is False  # identical: absorbed
    man.extra = dict(man.extra, poisoned=True)
    with pytest.raises(CommitRaceError):
        commit_once(store, man)
    mgr.close()


def test_host_processes_stay_off_the_accelerator(monkeypatch):
    """A chip belongs to one process and the launching trainer holds it:
    every host process is started on the CPU backend, whatever the
    launcher's own JAX_PLATFORMS says."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = host_proc.child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    src = env["PYTHONPATH"].split(os.pathsep)[0]
    assert os.path.isdir(os.path.join(src, "repro"))
