"""End-to-end checkpoint write-path AND restore-path benchmark: serial seed
path vs the pipelined parallel engine (core/pipeline.py), the streaming
fetch→decode→apply restore engine vs a serial chunk-by-chunk replica over a
read-throttled store, the sharded multi-host sweep (dist/shard_writer.py —
1/2/4/8 simulated hosts on a shared aggregate link vs per-host links), the
remote object-store section (core/remote_store.py — protocol overhead vs a
ThrottledStore at the same modelled link, plus a seeded fault sweep that
measures retry amplification as wire-bytes / logical-bytes), the partial-
vs-full host-loss recovery sweep (dist/recovery.py — shard replay vs
whole-model restore at 2/4/8 hosts over the modelled read link), plus the
bit-packing microbench. Writes ``BENCH_write_path.json``.

  PYTHONPATH=src python benchmarks/write_path.py [--tiny] [--restore-only]
                                                 [--out PATH]

Reported per mode: wall seconds, end-to-end GB/s over the snapshot bytes,
per-stage busy split, pipeline occupancy. The serial write baseline is a
faithful replica of the seed manager loop: per-chunk jitted quantization,
bit-matrix reference packer, one blocking put per chunk on a single thread.
The serial restore baseline fetches and decodes the recovery chain one
chunk at a time (the seed had no read pipeline), over the same
latency+bandwidth read model as the streaming engine. Byte-identity is
asserted in-bench: fused-pack vs host-pack writes, and serial vs streaming
vs unthrottled restores. ``--restore-only`` runs just the restore section
(the CI gate: it exits nonzero if any restore is not byte-identical).
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict

import numpy as np

import jax.numpy as jnp

from repro.core import (
    CheckNRunManager,
    CheckpointConfig,
    InMemoryStore,
    QuantConfig,
    ThrottledStore,
    host_link,
    quantize,
)
from repro.core import integrity
from repro.core import manifest as mf
from repro.core import packing
from repro.core import trace
from repro.core.snapshot import Snapshot
from repro.core.storage import ObjectStore
from repro.launch.compile_cache import enable_compile_cache


def make_workload(tables: int, rows: int, dim: int, seed: int = 0,
                  dense_dim: int = 512) -> Snapshot:
    rng = np.random.default_rng(seed)
    tabs = {f"emb{i}": (rng.normal(size=(rows, dim))
                        * rng.gamma(1.0, 1.0, (rows, 1))).astype(np.float32)
            for i in range(tables)}
    row_state = {n: {"acc": np.abs(rng.normal(size=rows)).astype(np.float32)}
                 for n in tabs}
    touched = {n: np.ones(rows, bool) for n in tabs}
    dense = {"top_mlp/w": rng.normal(size=(dense_dim, dense_dim)).astype(np.float32)}
    return Snapshot(step=1, tables=tabs, row_state=row_state,
                    touched=touched, dense=dense, extra={})


# ---------------------------------------------------------------------------
# Serial seed-path replica (per-chunk quantize, reference packer, 1 writer)
# ---------------------------------------------------------------------------


def serial_seed_write(snap: Snapshot, store: ObjectStore,
                      qcfg: QuantConfig, chunk_rows: int) -> Dict[str, float]:
    t_start = time.monotonic()
    build_s = write_s = 0.0
    total = 0
    qcfg = qcfg.resolve() if qcfg is not None else None
    tables: Dict[str, mf.TableRecord] = {}
    for name, tab in snap.tables.items():
        rows, dim = tab.shape
        sel = np.arange(rows, dtype=np.uint32)
        aux = snap.row_state.get(name, {})
        chunks = []
        for lo in range(0, len(sel), chunk_rows):
            idx = sel[lo: lo + chunk_rows]
            t0 = time.monotonic()
            parts, sections, off = [], {}, 0

            def add(nm, b):
                nonlocal off
                sections[nm] = [off, len(b)]
                parts.append(b)
                off += len(b)

            if qcfg is not None:
                q = quantize(jnp.asarray(tab[idx]), qcfg)
                add("scale", np.asarray(q.scale, dtype=np.float16).tobytes())
                add("zero", np.asarray(q.zero, dtype=np.float16).tobytes())
                add("codes", packing.pack_bits_reference(
                    np.asarray(q.codes), qcfg.bits))
            else:
                add("values", np.ascontiguousarray(
                    tab[idx], dtype=np.float32).tobytes())
            for a_name, a_arr in aux.items():
                add(f"aux:{a_name}", np.ascontiguousarray(a_arr[idx]).tobytes())
            payload = b"".join(parts)
            build_s += time.monotonic() - t0
            key = f"{mf.chunk_prefix(1)}{name}/{lo // chunk_rows:06d}.bin"
            t0 = time.monotonic()
            store.put(key, payload)
            write_s += time.monotonic() - t0
            chunks.append(mf.ChunkRecord(
                key=key, n_rows=int(len(idx)), nbytes=len(payload),
                crc32=ObjectStore.checksum(payload), sections=sections,
                row_range=[int(idx[0]), int(idx[-1]) + 1]))
            total += len(payload)
        tables[name] = mf.TableRecord(
            rows=rows, dim=dim, dtype="float32",
            bits=qcfg.bits if qcfg else None,
            method=qcfg.method if qcfg else None,
            row_state={a: str(v.dtype) for a, v in aux.items()},
            chunks=chunks, meta_dtype="float16" if qcfg else None)
    dense = {}
    for key_name, arr in snap.dense.items():
        data = np.ascontiguousarray(arr).tobytes()
        key = f"{mf.chunk_prefix(1)}dense/{key_name.replace('/', '__')}.bin"
        t0 = time.monotonic()
        store.put(key, data)
        write_s += time.monotonic() - t0
        dense[key_name] = mf.DenseRecord(
            key=key, shape=list(arr.shape), dtype=str(arr.dtype),
            nbytes=len(data), crc32=ObjectStore.checksum(data))
        total += len(data)
    man = mf.Manifest(step=1, kind="full", base_step=1, prev_step=None,
                      quant=None, policy={"name": "full_only"},
                      tables=tables, dense=dense, extra={}, nbytes_total=total,
                      wall_time_s=time.monotonic() - t_start,
                      created_unix=time.time())
    mf.commit(store, man)
    return dict(wall_s=time.monotonic() - t_start, build_s=build_s,
                write_s=write_s, nbytes=total)


# ---------------------------------------------------------------------------
# Benchmark drivers
# ---------------------------------------------------------------------------


def bench_end_to_end(args, qcfg: QuantConfig) -> dict:
    snap = make_workload(args.tables, args.rows, args.dim)
    input_gb = snap.total_param_bytes() / 1e9

    # warm the jit caches out-of-band so neither mode pays compile time in
    # the measured region (shapes must match: serial jits per chunk shape,
    # the engine jits per table-selection shape)
    warm = make_workload(1, args.rows, args.dim, seed=9)
    warm_store = InMemoryStore()
    serial_seed_write(warm, warm_store, qcfg, args.chunk_rows)
    mgr_w = CheckNRunManager(warm_store, CheckpointConfig(
        policy="full_only", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows))
    mgr_w.save(warm).result()
    mgr_w.close()

    # best-of-N per mode: the box is small and shared, min wall is the
    # least-noise estimator for throughput benchmarks
    serial = None
    for _ in range(args.repeats):
        serial_store = InMemoryStore()
        r = serial_seed_write(snap, serial_store, qcfg, args.chunk_rows)
        if serial is None or r["wall_s"] < serial["wall_s"]:
            serial = r

    pipe_wall = res = None
    for i in range(args.repeats):
        pipe_store = InMemoryStore()
        mgr = CheckNRunManager(pipe_store, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows, encode_workers=args.encode_workers,
            write_workers=args.write_workers))
        t0 = time.monotonic()
        with trace.record():
            r = mgr.save(snap).result()
        wall = time.monotonic() - t0
        # device quantize(+pack) seconds: the save's cnr.save.quant spans
        quantize_s = sum(sp.seconds for sp in trace.drain()
                         if sp.name == "cnr.save.quant")
        if pipe_wall is None or wall < pipe_wall:
            # keep stats from the min-wall repeat
            pipe_wall, res, pipe_quant_s = wall, r, quantize_s
        if i < args.repeats - 1:
            mgr.close()

    # correctness 1: the fused device-packed write must be byte-identical
    # to the host pack_bits fallback (same quantizer, different packer)
    fb_store = InMemoryStore()
    fb_mgr = CheckNRunManager(fb_store, CheckpointConfig(
        policy="full_only", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows, fused_pack=False))
    fb_mgr.save(snap).result()
    fused_keys = list(pipe_store.list("chunks/"))
    if fused_keys != list(fb_store.list("chunks/")):
        raise AssertionError("fused vs host-pack chunk key sets differ")
    for k in fused_keys:
        if pipe_store.get(k) != fb_store.get(k):
            raise AssertionError(f"fused vs host-pack payload differs: {k}")
    fb_mgr.close()

    # correctness 2: restores must match the serial seed replica. The seed
    # replica quantizes through the original reference search; the engine
    # uses the fused op's r-space form — identical greedy decisions up to
    # f32 rounding ties, so adaptive tolerates a vanishing tie fraction
    # while uniform (search-free) must be exactly byte-identical.
    rs_serial = CheckNRunManager(serial_store, CheckpointConfig(
        policy="full_only", quant=qcfg)).restore()
    rs_pipe = mgr.restore()
    identical = True
    for name in snap.tables:
        a, b = rs_serial.tables[name], rs_pipe.tables[name]
        if not np.array_equal(a, b):
            identical = False
            frac = np.mean(a != b)
            if qcfg.method != "adaptive" or frac > 1e-3:
                raise AssertionError(
                    f"restore mismatch for table {name} ({frac:.2e})")
        if not np.array_equal(rs_serial.row_state[name]["acc"],
                              rs_pipe.row_state[name]["acc"]):
            raise AssertionError(f"restore mismatch for aux of {name}")
    for name in snap.dense:
        if not np.array_equal(rs_serial.dense[name], rs_pipe.dense[name]):
            raise AssertionError(f"restore mismatch for dense {name}")
    mgr.close()

    stats = res.pipeline_stats or {}
    return {
        "config": {
            "tables": args.tables, "rows": args.rows, "dim": args.dim,
            "chunk_rows": args.chunk_rows, "bits": qcfg.bits,
            "method": qcfg.method, "encode_workers": args.encode_workers,
            "write_workers": args.write_workers,
        },
        "input_gb": round(input_gb, 4),
        "serial_seed": {
            "wall_s": round(serial["wall_s"], 4),
            "build_s": round(serial["build_s"], 4),
            "write_s": round(serial["write_s"], 4),
            "gbps": round(input_gb / serial["wall_s"], 3),
        },
        "pipelined": {
            "wall_s": round(pipe_wall, 4),
            # busy times summed across workers — NOT comparable to the
            # serial mode's elapsed build_s/write_s; wall_s is the
            # apples-to-apples number
            "build_busy_s": round(res.build_time_s, 4),
            "write_busy_s": round(res.write_time_s, 4),
            "gbps": round(input_gb / pipe_wall, 3),
            "occupancy": {k: round(v, 3) for k, v in
                          stats.get("occupancy", {}).items()},
            "quantize_s": round(pipe_quant_s, 4),
        },
        "speedup_e2e": round(serial["wall_s"] / pipe_wall, 2),
        "fused_vs_hostpack_identical": True,
        "restored_identical": identical,
    }


def bench_sharded(args, qcfg: QuantConfig) -> dict:
    """Sharded multi-host sweep: 1/2/4/8 simulated hosts writing the same
    snapshot through a throttled store, modelled two ways —

      shared:   all hosts share ONE aggregate link (adding hosts cannot add
                bandwidth; two-phase commit overhead must stay ~free)
      per_host: every host gets its own link of the same bandwidth (the
                paper's decentralized-writer story: bandwidth scales with
                hosts, wall time ≈ 1/N)

    Every configuration's restore must be byte-identical to the unthrottled
    single-host restore of the same snapshot.
    """
    # embedding-dominated workload (tiny dense): dense params are written by
    # a single owner host, so a dense-heavy snapshot would serialize on one
    # link and mask the table-shard scaling the sweep measures
    snap = make_workload(args.tables, args.rows, args.dim, seed=3,
                         dense_dim=32)

    # reference: unthrottled single-host write → payload size + restore oracle
    ref_store = InMemoryStore()
    ref_mgr = CheckNRunManager(ref_store, CheckpointConfig(
        policy="full_only", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows))
    payload = ref_mgr.save(snap).result().nbytes
    ref = ref_mgr.restore()
    ref_mgr.close()

    bw = payload / args.shard_target_s  # per-link B/s: 1-host shared ≈ target
    sweep = []
    for n in args.num_hosts:
        # warm the jit caches for this host count's shard shapes so the
        # timed region measures the link model, not compilation
        warm_mgr = CheckNRunManager(InMemoryStore(), CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows, num_hosts=n,
            encode_workers=args.encode_workers,
            write_workers=args.write_workers))
        warm_mgr.save(snap).result()
        warm_mgr.close()
        row = {"num_hosts": n}
        for mode in ("shared", "per_host"):
            store = ThrottledStore(
                InMemoryStore(), write_bytes_per_sec=bw,
                num_links=(n if mode == "per_host" else 1),
                link_of=(host_link if mode == "per_host" else None))
            mgr = CheckNRunManager(store, CheckpointConfig(
                policy="full_only", quant=qcfg, async_write=False,
                chunk_rows=args.chunk_rows, num_hosts=n,
                encode_workers=args.encode_workers,
                write_workers=args.write_workers))
            t0 = time.monotonic()
            mgr.save(snap).result()
            wall = time.monotonic() - t0
            rs = mgr.restore()
            for name in snap.tables:
                if not np.array_equal(ref.tables[name], rs.tables[name]):
                    raise AssertionError(
                        f"sharded restore mismatch: {name} ({n} hosts, {mode})")
                if not np.array_equal(ref.row_state[name]["acc"],
                                      rs.row_state[name]["acc"]):
                    raise AssertionError(
                        f"sharded aux mismatch: {name} ({n} hosts, {mode})")
            for name in snap.dense:  # per-host dense ownership is new here
                if not np.array_equal(ref.dense[name], rs.dense[name]):
                    raise AssertionError(
                        f"sharded dense mismatch: {name} ({n} hosts, {mode})")
            mgr.close()
            row[mode] = {"wall_s": round(wall, 4),
                         "mbps": round(payload / wall / 1e6, 2)}
        row["per_host_speedup"] = round(
            row["shared"]["wall_s"] / row["per_host"]["wall_s"], 2)
        sweep.append(row)
    return {
        "config": {"tables": args.tables, "rows": args.rows, "dim": args.dim,
                   "bits": qcfg.bits, "method": qcfg.method,
                   "payload_bytes": payload,
                   "per_link_bw_mbps": round(bw / 1e6, 2)},
        "sweep": sweep,
        "restored_identical": True,
    }


def bench_multiprocess(args, qcfg: QuantConfig) -> dict:
    """Real-process host sweep: the same snapshot written by N OS processes
    (``repro.dist.host_proc``, coordinator-less last-voter commit) over a
    shared LocalFSStore, vs the thread-simulated engine over the same
    store. Process wall includes spawn + interpreter/jax import — the cost
    of REAL host isolation — so it is reported alongside, not speedup-
    compared. Every configuration's restore must be byte-identical to the
    unthrottled single-host reference restore."""
    import shutil
    import tempfile

    from repro.core import CheckNRunManager as Mgr
    from repro.core import LocalFSStore

    snap = make_workload(args.tables, args.rows, args.dim, seed=3,
                         dense_dim=32)
    ref_store = InMemoryStore()
    ref_mgr = Mgr(ref_store, CheckpointConfig(
        policy="full_only", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows))
    payload = ref_mgr.save(snap).result().nbytes
    ref = ref_mgr.restore()
    ref_mgr.close()

    def check(rs, label):
        for name in snap.tables:
            if not np.array_equal(ref.tables[name], rs.tables[name]):
                raise AssertionError(f"multiprocess mismatch: {name} ({label})")
            if not np.array_equal(ref.row_state[name]["acc"],
                                  rs.row_state[name]["acc"]):
                raise AssertionError(f"multiprocess aux mismatch: {name} "
                                     f"({label})")
        for name in snap.dense:
            if not np.array_equal(ref.dense[name], rs.dense[name]):
                raise AssertionError(f"multiprocess dense mismatch: {name} "
                                     f"({label})")

    sweep = []
    for n in args.mp_hosts:
        tmp = tempfile.mkdtemp(prefix="cnr-bench-mp-")
        try:
            row = {"num_hosts": n}
            for mode in ("threads", "processes"):
                store = LocalFSStore(os.path.join(tmp, mode))
                mgr = Mgr(store, CheckpointConfig(
                    policy="full_only", quant=qcfg, async_write=False,
                    chunk_rows=args.chunk_rows, num_hosts=n,
                    multiprocess=(mode == "processes"), spill_dir=tmp,
                    encode_workers=args.encode_workers,
                    write_workers=args.write_workers))
                t0 = time.monotonic()
                res = mgr.save(snap).result()
                wall = time.monotonic() - t0
                check(mgr.restore(), f"{n} hosts, {mode}")
                entry = {"wall_s": round(wall, 4),
                         "mbps": round(payload / wall / 1e6, 2)}
                if mode == "processes":
                    entry["exit_codes"] = res.pipeline_stats["exit_codes"]
                row[mode] = entry
                mgr.close()
            sweep.append(row)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return {
        "config": {"tables": args.tables, "rows": args.rows, "dim": args.dim,
                   "bits": qcfg.bits, "method": qcfg.method,
                   "payload_bytes": payload},
        "note": "process wall includes per-host interpreter+jax spawn "
                "(the price of real host isolation; amortized over a "
                "training job's lifetime in production)",
        "sweep": sweep,
        "restored_identical": True,
    }


def bench_remote(args, qcfg: QuantConfig) -> dict:
    """Remote object-store section: the same sharded save driven through
    ``RemoteObjectStore`` (core/remote_store.py) three ways —

      clean:     in-process ServerTransport, no faults — the pure protocol
                 overhead of PUT/GET/LIST + read-after-write verify on the
                 vote/manifest keys
      throttled: ThrottledTransport at the same link bandwidth as a
                 ThrottledStore baseline (identical LinkModel arithmetic),
                 so the wall-clock delta is protocol overhead, not model
                 mismatch
      faulty:    seeded FaultyTransport at increasing error rates — every
                 retransmission pays wire bytes, so retry amplification
                 (wire bytes sent / logical bytes written) is measured,
                 not inferred

    Every configuration's restore must be byte-identical to the
    unthrottled in-memory reference restore."""
    from repro.core.remote_store import (
        FaultSpec,
        RemoteObjectStore,
        RetryPolicy,
        ServerTransport,
        ThrottledTransport,
        wrap_faulty,
    )

    snap = make_workload(args.tables, args.rows, args.dim, seed=3,
                         dense_dim=32)
    ref_store = InMemoryStore()
    ref_mgr = CheckNRunManager(ref_store, CheckpointConfig(
        policy="full_only", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows))
    payload = ref_mgr.save(snap).result().nbytes
    ref = ref_mgr.restore()
    ref_mgr.close()

    retry = RetryPolicy(attempts=8, base_s=0.002, cap_s=0.05)

    def run_one(store, label):
        mgr = CheckNRunManager(store, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows, num_hosts=2,
            encode_workers=args.encode_workers,
            write_workers=args.write_workers))
        t0 = time.monotonic()
        mgr.save(snap).result()
        wall = time.monotonic() - t0
        rs = mgr.restore()
        for name in snap.tables:
            if not np.array_equal(ref.tables[name], rs.tables[name]):
                raise AssertionError(f"remote restore mismatch: {name} "
                                     f"({label})")
            if not np.array_equal(ref.row_state[name]["acc"],
                                  rs.row_state[name]["acc"]):
                raise AssertionError(f"remote aux mismatch: {name} ({label})")
        for name in snap.dense:
            if not np.array_equal(ref.dense[name], rs.dense[name]):
                raise AssertionError(f"remote dense mismatch: {name} "
                                     f"({label})")
        mgr.close()
        return wall

    # clean protocol overhead (multipart exercised via a small part size)
    clean_store = RemoteObjectStore(ServerTransport(), retry=retry,
                                    part_size=args.remote_part_size)
    clean_wall = run_one(clean_store, "clean")

    # bandwidth-capped: ThrottledStore baseline vs remote over the same link
    bw = payload / args.shard_target_s
    base_wall = run_one(ThrottledStore(InMemoryStore(),
                                       write_bytes_per_sec=bw),
                        "throttled-store")
    thr_store = RemoteObjectStore(
        ThrottledTransport(ServerTransport(), write_bytes_per_sec=bw),
        retry=retry, part_size=args.remote_part_size)
    thr_wall = run_one(thr_store, "throttled-remote")

    # seeded fault sweep: wall + retry amplification at rising error rates
    sweep = []
    for rate in args.remote_error_rates:
        store = RemoteObjectStore(ServerTransport(), retry=retry,
                                  part_size=args.remote_part_size)
        inj = wrap_faulty(store, FaultSpec(
            seed=7, error_rate=rate, partial_put_rate=rate / 4))
        wall = run_one(store, f"faulty@{rate}")
        logical = store.counters.snapshot()["bytes_written"]
        s = store.stats.snapshot()
        sweep.append({
            "error_rate": rate,
            "wall_s": round(wall, 4),
            "injected_faults": inj.injected,
            "requests": s["requests"],
            "retries": s["retries"],
            "write_amplification": round(
                store.stats.write_amplification(logical), 3),
        })

    return {
        "config": {"tables": args.tables, "rows": args.rows, "dim": args.dim,
                   "bits": qcfg.bits, "method": qcfg.method,
                   "payload_bytes": payload,
                   "part_size": args.remote_part_size,
                   "link_bw_mbps": round(bw / 1e6, 2)},
        "clean": {"wall_s": round(clean_wall, 4),
                  "mbps": round(payload / clean_wall / 1e6, 2)},
        "throttled": {
            "store_wall_s": round(base_wall, 4),
            "remote_wall_s": round(thr_wall, 4),
            # remote over the identical link model: ratio is the protocol
            # (request framing + vote/manifest verify reads) overhead
            "protocol_overhead": round(thr_wall / base_wall, 2),
        },
        "fault_sweep": sweep,
        "restored_identical": True,
    }


def _touch_snap(base: Snapshot, step: int, frac: float, seed: int) -> Snapshot:
    """Derive an incremental snapshot: mutate a random ``frac`` of each
    table's rows and mark them touched."""
    rng = np.random.default_rng(seed)
    tabs, touched, row_state = {}, {}, {}
    for name, tab in base.tables.items():
        rows = tab.shape[0]
        n = max(1, int(rows * frac))
        idx = rng.choice(rows, size=n, replace=False)
        t = tab.copy()
        t[idx] += rng.normal(size=(n, tab.shape[1])).astype(np.float32)
        tabs[name] = t
        mask = np.zeros(rows, bool)
        mask[idx] = True
        touched[name] = mask
        acc = base.row_state[name]["acc"].copy()
        acc[idx] = np.abs(rng.normal(size=n)).astype(np.float32)
        row_state[name] = {"acc": acc}
    return Snapshot(step=step, tables=tabs, row_state=row_state,
                    touched=touched, dense=base.dense, extra={})


def serial_seed_restore(mgr: CheckNRunManager, store: ObjectStore,
                        step: int) -> Dict:
    """Seed-style restore replica: walk the recovery chain one chunk at a
    time — fetch, then decode, then scatter, strictly sequentially on one
    thread (no prefetch, no decode overlap). Decoding reuses the manager's
    chunk decoder so the comparison isolates ORCHESTRATION, not decode
    implementation differences."""
    t0 = time.monotonic()
    chain = mf.recovery_chain(store, step)
    tables: Dict[str, np.ndarray] = {}
    row_state: Dict[str, Dict[str, np.ndarray]] = {}
    fetch_s = decode_s = 0.0
    for man in chain:
        for name, rec in man.tables.items():
            if name not in tables:
                tables[name] = np.zeros((rec.rows, rec.dim), np.float32)
                row_state[name] = {}
            for ch in rec.chunks:
                if ch.n_rows == 0:
                    continue
                t1 = time.monotonic()
                data = store.get(ch.key)
                fetch_s += time.monotonic() - t1
                t1 = time.monotonic()
                decoded = mgr._decode_chunk(man.step, name, rec, ch, data)
                mgr._apply_decoded(tables[name], row_state[name], rec, ch,
                                   0, decoded)
                decode_s += time.monotonic() - t1
    dense: Dict[str, np.ndarray] = {}
    final = chain[-1]
    for key_name, drec in final.dense.items():
        t1 = time.monotonic()
        data = store.get(drec.key)
        fetch_s += time.monotonic() - t1
        dense[key_name] = mgr._decode_dense(final.step, key_name, drec, data)
    return dict(wall_s=time.monotonic() - t0, fetch_s=fetch_s,
                decode_s=decode_s, tables=tables, row_state=row_state,
                dense=dense, chain_len=len(chain))


def bench_restore(args, qcfg: QuantConfig) -> dict:
    """Chain-restore benchmark over a network-bound read model.

    Builds one full checkpoint + ``--restore-chain`` increments, then
    restores the chain three ways from the same blobs:

      unthrottled:  free reads (the byte-identity oracle)
      serial:       seed replica — one chunk at a time, each GET paying
                    first-byte latency + shared-link bandwidth, decode
                    after each fetch (no overlap anywhere)
      streaming:    the engine — parallel fetches (latency overlaps,
                    bandwidth shared), parallel decode, ordered apply,
                    increments prefetched while the baseline decodes

    All three restores must be byte-identical.
    """
    base = make_workload(args.tables, args.rows, args.dim, seed=7,
                         dense_dim=128)
    store = InMemoryStore()
    # consecutive increments: every step stays in the recovery chain, so
    # the restore replays chain_len manifests (real chain-replay streaming)
    mgr = CheckNRunManager(store, CheckpointConfig(
        policy="consecutive", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows,
        restore_workers=args.restore_workers,
        decode_workers=args.decode_workers))
    mgr.save(base).result()
    snap = base
    for i in range(args.restore_chain):
        snap = _touch_snap(snap, 2 + i, args.restore_touch, seed=20 + i)
        mgr.save(snap).result()
    last_step = 1 + args.restore_chain

    # oracle: unthrottled streaming restore
    ref = mgr.restore(last_step)

    def throttled():
        # wrap the already-written blobs in a read-throttled view
        return ThrottledStore(
            store, write_bytes_per_sec=1e12,
            read_bytes_per_sec=args.read_mbps * 1e6,
            read_latency_s=args.read_latency_ms / 1e3)

    chain_bytes = sum(store.size(k) for k in store.list("chunks/"))

    # serial seed replica (best of N — the model is deterministic-ish but
    # the box is shared)
    serial = None
    for _ in range(args.restore_repeats):
        r = serial_seed_restore(mgr, throttled(), last_step)
        if serial is None or r["wall_s"] < serial["wall_s"]:
            serial = r

    # streaming engine
    stream_wall = stream_rs = None
    for _ in range(args.restore_repeats):
        smgr = CheckNRunManager(throttled(), CheckpointConfig(
            policy="consecutive", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows,
            restore_workers=args.restore_workers,
            decode_workers=args.decode_workers))
        t0 = time.monotonic()
        rs = smgr.restore(last_step)
        wall = time.monotonic() - t0
        if stream_wall is None or wall < stream_wall:
            stream_wall, stream_rs = wall, rs
        smgr.close()

    for name in ref.tables:
        for other, label in ((serial["tables"][name], "serial"),
                             (stream_rs.tables[name], "streaming")):
            if not np.array_equal(ref.tables[name], other):
                raise AssertionError(f"{label} restore mismatch: {name}")
        for other, label in ((serial["row_state"][name]["acc"], "serial"),
                             (stream_rs.row_state[name]["acc"], "streaming")):
            if not np.array_equal(ref.row_state[name]["acc"], other):
                raise AssertionError(f"{label} aux mismatch: {name}")
    for name in ref.dense:
        if not np.array_equal(ref.dense[name], serial["dense"][name]):
            raise AssertionError(f"serial dense mismatch: {name}")
        if not np.array_equal(ref.dense[name], stream_rs.dense[name]):
            raise AssertionError(f"streaming dense mismatch: {name}")
    mgr.close()

    # integrity gate: a deep scan (size + crc32 + hash32 of every chunk in
    # the chain) over the unthrottled blobs must come back clean — the same
    # pass `ckpt scan` runs, timed here so scan-cost regressions surface
    t0 = time.monotonic()
    scan = integrity.scan_store(store, deep=True)
    scan_wall = time.monotonic() - t0
    if not scan.ok:
        raise AssertionError(
            f"integrity scan found problems: {[p.to_dict() for p in scan.problems]}")
    scan_stats = {
        "wall_s": round(scan_wall, 4),
        "chunks": sum(r.chunks_checked for r in scan.steps.values()),
        "bytes": sum(r.bytes_checked for r in scan.steps.values()),
        "ok": True,
    }

    return {
        "config": {
            "tables": args.tables, "rows": args.rows, "dim": args.dim,
            "chunk_rows": args.chunk_rows, "bits": qcfg.bits,
            "method": qcfg.method, "chain_len": 1 + args.restore_chain,
            "touch_frac": args.restore_touch,
            "chain_bytes": chain_bytes,
            "read_mbps": args.read_mbps,
            "read_latency_ms": args.read_latency_ms,
            "fetch_workers": args.restore_workers,
            "decode_workers": args.decode_workers,
        },
        "serial_seed": {
            "wall_s": round(serial["wall_s"], 4),
            "fetch_s": round(serial["fetch_s"], 4),
            "decode_s": round(serial["decode_s"], 4),
            "mbps": round(chain_bytes / serial["wall_s"] / 1e6, 2),
        },
        "streaming": {
            "wall_s": round(stream_wall, 4),
            "mbps": round(chain_bytes / stream_wall / 1e6, 2),
            "pipeline": {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in (stream_rs.stats or {}).items()
                         if k != "busy"},
        },
        "speedup_restore": round(serial["wall_s"] / stream_wall, 2),
        "restored_identical": True,
        "integrity_scan": scan_stats,
    }


def bench_recovery(args, qcfg: QuantConfig) -> dict:
    """Partial vs full recovery after a host loss (docs/partial_recovery.md),
    over the same network-bound read model as the restore section.

    For each host count N the same embedding-dominated snapshot is saved
    sharded N ways, then recovered two ways from a read-throttled view of
    the same blobs:

      full:     the classical response — restore the WHOLE model
      partial:  fence the victim and replay ONLY its shard chain via the
                recovery supervisor (``restore_part``)

    The headline is the bytes ratio: partial recovery must fetch ≈ the
    victim's shard (1/N of the tables, plus dense + manifest overhead),
    not the model — that is the ``partial_recovery_bytes_o_shard``
    acceptance flag. Wall time follows bytes on a bandwidth-bound link.
    Correctness: the partial result must equal the full restore's slice of
    the victim's row ranges."""
    from repro.dist import recovery as rcv

    snap = make_workload(args.tables, args.rows, args.dim, seed=3,
                         dense_dim=32)
    victim = 1
    sweep = []
    for n in args.recovery_hosts:
        store = InMemoryStore()
        mgr = CheckNRunManager(store, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows, num_hosts=n,
            encode_workers=args.encode_workers,
            write_workers=args.write_workers))
        mgr.save(snap).result()
        mgr.close()

        def throttled():
            return ThrottledStore(
                store, write_bytes_per_sec=1e12,
                read_bytes_per_sec=args.read_mbps * 1e6,
                read_latency_s=args.read_latency_ms / 1e3)

        # full restore (the classical recovery everyone pays today)
        view = throttled()
        fmgr = CheckNRunManager(view, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows,
            restore_workers=args.restore_workers,
            decode_workers=args.decode_workers))
        b0 = view.counters.snapshot()["bytes_read"]
        t0 = time.monotonic()
        full = fmgr.restore(1)
        full_wall = time.monotonic() - t0
        full_bytes = view.counters.snapshot()["bytes_read"] - b0
        fmgr.close()

        # partial: supervisor fences the victim, replays one shard chain
        view = throttled()
        pmgr = CheckNRunManager(view, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows,
            restore_workers=args.restore_workers,
            decode_workers=args.decode_workers))
        sup = rcv.RecoverySupervisor(view, n)
        b0 = view.counters.snapshot()["bytes_read"]
        t0 = time.monotonic()
        rs = sup.recover(pmgr, victim, step=1)
        part_wall = time.monotonic() - t0
        part_bytes = view.counters.snapshot()["bytes_read"] - b0
        pmgr.close()
        if rs.extra["recovery"]["kind"] != "partial":
            raise AssertionError(
                f"recovery degraded to full at {n} hosts: "
                f"{rs.extra.get('recovery_fallback_reason')}")
        for name in snap.tables:
            lo, hi = rs.extra["shard"]["row_range"][name]
            if not np.array_equal(rs.tables[name], full.tables[name][lo:hi]):
                raise AssertionError(
                    f"partial recovery mismatch: {name} ({n} hosts)")
        shard_bytes = rcv.shard_nbytes(store, victim, 1)
        sweep.append({
            "num_hosts": n,
            "full": {"wall_s": round(full_wall, 4), "bytes": full_bytes},
            "partial": {"wall_s": round(part_wall, 4), "bytes": part_bytes,
                        "shard_payload_bytes": shard_bytes},
            "bytes_ratio": round(part_bytes / full_bytes, 3),
            "wall_speedup": round(full_wall / part_wall, 2),
            # O(shard): the fetch may exceed the pure shard payload only
            # by metadata (global manifest + part JSON) and dense params
            "bytes_o_shard": part_bytes / full_bytes <= 1.0 / n + 0.15,
        })
    return {
        "config": {"tables": args.tables, "rows": args.rows, "dim": args.dim,
                   "bits": qcfg.bits, "method": qcfg.method,
                   "read_mbps": args.read_mbps,
                   "read_latency_ms": args.read_latency_ms,
                   "victim_host": victim},
        "sweep": sweep,
        "partial_matches_full_slice": True,
    }


def bench_resharding(args, qcfg: QuantConfig) -> dict:
    """Elastic N→M restore (docs/resharding.md) over the throttled read
    model: save the snapshot sharded ``n_src`` ways, then range-read EVERY
    target shard of an ``n_tgt``-host layout via
    ``restore_part(..., num_hosts=)`` — no rewrite of the chain, the
    planner resolves each target range across the union of source shards.

    Gates: each new host fetches ≈ its OWN target shard (bounded by the
    range plan's own cost estimate, ``shard_nbytes(..., num_hosts=)``,
    plus metadata overhead — NOT O(model)), and every target shard is
    byte-identical to the full restore's slice of its row ranges."""
    from repro.dist import recovery as rcv

    snap = make_workload(args.tables, args.rows, args.dim, seed=5,
                         dense_dim=32)
    meta_slack = 262_144  # global manifest + part JSONs per read
    sweep = []
    matches = True
    for n_src, n_tgt in args.reshard_pairs:
        store = InMemoryStore()
        mgr = CheckNRunManager(store, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=args.chunk_rows, num_hosts=n_src,
            encode_workers=args.encode_workers,
            write_workers=args.write_workers))
        mgr.save(snap).result()

        # unthrottled full restore: the byte-identity reference
        full = mgr.restore(1)
        full_bytes = sum(m.nbytes_total for m in
                         mf.recovery_chain(store, 1))
        mgr.close()

        hosts = []
        o_shard = True
        for h in range(n_tgt):
            view = ThrottledStore(
                store, write_bytes_per_sec=1e12,
                read_bytes_per_sec=args.read_mbps * 1e6,
                read_latency_s=args.read_latency_ms / 1e3)
            pmgr = CheckNRunManager(view, CheckpointConfig(
                policy="full_only", quant=qcfg, async_write=False,
                chunk_rows=args.chunk_rows,
                restore_workers=args.restore_workers,
                decode_workers=args.decode_workers))
            budget = rcv.shard_nbytes(store, h, 1, num_hosts=n_tgt)
            b0 = view.counters.snapshot()["bytes_read"]
            t0 = time.monotonic()
            rs = pmgr.restore_part(h, 1, num_hosts=n_tgt)
            wall = time.monotonic() - t0
            nbytes = view.counters.snapshot()["bytes_read"] - b0
            pmgr.close()
            if not rs.extra["shard"]["resharded"]:
                raise AssertionError(
                    f"{n_src}->{n_tgt} host {h}: read not flagged resharded")
            for name in snap.tables:
                lo, hi = rs.extra["shard"]["row_range"][name]
                if not np.array_equal(rs.tables[name],
                                      full.tables[name][lo:hi]):
                    matches = False
            ok = nbytes <= budget + meta_slack
            o_shard = o_shard and ok
            hosts.append({"host": h, "wall_s": round(wall, 4),
                          "bytes": nbytes, "planned_bytes": budget,
                          "bytes_o_shard": ok})
        sweep.append({
            "src_hosts": n_src, "tgt_hosts": n_tgt,
            "full_chain_bytes": full_bytes,
            "hosts": hosts,
            "bytes_o_shard": o_shard,
            # every target host could restore CONCURRENTLY at ≈ 1/M of
            # the payload each; the sum stays ≈ one full restore
            "sum_bytes_ratio": round(
                sum(r["bytes"] for r in hosts) / max(full_bytes, 1), 3),
        })
    return {
        "config": {"tables": args.tables, "rows": args.rows,
                   "dim": args.dim, "bits": qcfg.bits,
                   "method": qcfg.method, "read_mbps": args.read_mbps,
                   "pairs": [list(p) for p in args.reshard_pairs]},
        "sweep": sweep,
        "matches_full_slice": matches,
    }


def bench_serving(args, qcfg: QuantConfig) -> dict:
    """Publisher/subscriber serving fleet (docs/serving.md): N replica
    subscribers track one training job over the throttled read model.

    Each replica pays the model ONCE (the initial full sync); every
    subsequent refresh must cost ≈ the step's touched-row payload — the
    commit-time delta index's own estimate plus a metadata allowance —
    regardless of model size. That is the ``serving_bytes_o_touched``
    acceptance flag. Freshness: every replica is at lag 0 after its poll.
    Correctness: after the run every replica's served tables and dense
    params are byte-identical to a cold ``restore(head)``
    (``serving_matches_restore``)."""
    from repro.serve import CheckpointSubscriber
    from repro.serve.delta_index import catchup_cost

    base = make_workload(args.tables, args.rows, args.dim, seed=11,
                         dense_dim=32)
    store = InMemoryStore()
    mgr = CheckNRunManager(store, CheckpointConfig(
        policy="consecutive", quant=qcfg, async_write=False,
        chunk_rows=args.chunk_rows,
        encode_workers=args.encode_workers,
        write_workers=args.write_workers))
    mgr.save(base).result()
    model_bytes = sum(m.nbytes_total for m in mf.recovery_chain(store, 1))

    def throttled():
        return ThrottledStore(
            store, write_bytes_per_sec=1e12,
            read_bytes_per_sec=args.read_mbps * 1e6,
            read_latency_s=args.read_latency_ms / 1e3)

    views = [throttled() for _ in range(args.serve_replicas)]
    subs = [CheckpointSubscriber(v, fetch_workers=args.restore_workers,
                                 decode_workers=args.decode_workers)
            for v in views]
    full_sync = []
    for v, sub in zip(views, subs):
        b0 = v.counters.snapshot()["bytes_read"]
        t0 = time.monotonic()
        applied = sub.poll_once()
        full_sync.append({
            "applied": applied,
            "wall_s": round(time.monotonic() - t0, 4),
            "bytes": v.counters.snapshot()["bytes_read"] - b0})

    meta_slack = 262_144  # manifest JSON + rounding per refresh
    snap = base
    sweep = []
    o_touched = True
    for i in range(args.serve_steps):
        step = 2 + i
        snap = _touch_snap(snap, step, args.serve_touch, seed=40 + i)
        mgr.save(snap).result()
        touched = catchup_cost([mf.load(store, step)])
        replicas = []
        for v, sub in zip(views, subs):
            b0 = v.counters.snapshot()["bytes_read"]
            t0 = time.monotonic()
            applied = sub.poll_once()
            nbytes = v.counters.snapshot()["bytes_read"] - b0
            ok = bool(applied) and nbytes <= touched["nbytes"] + meta_slack
            o_touched = o_touched and ok
            replicas.append({
                "wall_s": round(time.monotonic() - t0, 4),
                "bytes": nbytes,
                "lag_steps": sub.health.lag_steps,
                "bytes_o_touched": ok})
        sweep.append({
            "step": step,
            "touched_payload_bytes": touched["nbytes"],
            "touched_rows": touched["rows_touched"],
            "replicas": replicas,
            # the headline: refresh cost as a fraction of re-shipping
            # the model to every replica each step
            "bytes_vs_model": round(
                max(r["bytes"] for r in replicas) / max(model_bytes, 1),
                4)})
    head = 1 + args.serve_steps
    mgr.close()

    # differential: every replica byte-identical to a cold restore(head)
    rmgr = CheckNRunManager(store, CheckpointConfig(
        policy="consecutive", quant=qcfg, async_write=False,
        restore_workers=args.restore_workers,
        decode_workers=args.decode_workers))
    ref = rmgr.restore(head)
    rmgr.close()
    matches = True
    for sub in subs:
        with sub.server.pinned() as view:
            if view.step != head:
                matches = False
                continue
            for name, want in ref.tables.items():
                if not np.array_equal(
                        view.lookup(name, np.arange(want.shape[0])), want):
                    matches = False
            for name, want in ref.dense.items():
                if not np.array_equal(view.dense(name), want):
                    matches = False
    return {
        "config": {"tables": args.tables, "rows": args.rows,
                   "dim": args.dim, "bits": qcfg.bits,
                   "method": qcfg.method, "replicas": args.serve_replicas,
                   "steps": args.serve_steps, "touch": args.serve_touch,
                   "read_mbps": args.read_mbps,
                   "read_latency_ms": args.read_latency_ms},
        "model_bytes": model_bytes,
        "full_sync": full_sync,
        "sweep": sweep,
        "bytes_o_touched": o_touched,
        "matches_restore": matches,
    }


def bench_packing(n_codes: int, extra_bits: int = 4) -> dict:
    rng = np.random.default_rng(0)
    out = {}
    for bits in sorted({2, 3, 4, 8} | {extra_bits}):
        codes = rng.integers(0, 1 << bits, size=n_codes).astype(np.uint8)
        # median of 3 to de-noise
        told = min(_time(lambda: packing.pack_bits_reference(codes, bits))
                   for _ in range(3))
        tnew = min(_time(lambda: packing.pack_bits(codes, bits))
                   for _ in range(3))
        buf = packing.pack_bits(codes, bits)
        tuold = min(_time(lambda: packing.unpack_bits_reference(buf, bits, n_codes))
                    for _ in range(3))
        tunew = min(_time(lambda: packing.unpack_bits(buf, bits, n_codes))
                    for _ in range(3))
        out[f"{bits}bit"] = {
            "pack_ref_s": round(told, 5), "pack_s": round(tnew, 5),
            "pack_speedup": round(told / max(tnew, 1e-9), 1),
            "unpack_ref_s": round(tuold, 5), "unpack_s": round(tunew, 5),
            "unpack_speedup": round(tuold / max(tunew, 1e-9), 1),
            "pack_gbps": round(n_codes / max(tnew, 1e-9) / 1e9, 2),
        }
    return out


def _time(fn):
    t0 = time.monotonic()
    fn()
    return time.monotonic() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables", type=int, default=4)
    ap.add_argument("--rows", type=int, default=131072)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--chunk-rows", type=int, default=16384)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--method", default="uniform_asym",
                    help="uniform_asym (headline) | adaptive")
    ap.add_argument("--encode-workers", type=int, default=2)
    # 2 by default: puts on an InMemoryStore are memcpy-fast, and on the
    # small shared CI boxes extra writer threads only add scheduler noise
    ap.add_argument("--write-workers", type=int, default=2)
    ap.add_argument("--pack-codes", type=int, default=16_777_216)
    ap.add_argument("--repeats", type=int, default=5,
                    help="best-of-N timing per mode")
    ap.add_argument("--num-hosts", default="1,2,4,8",
                    help="comma-separated simulated host counts for the "
                         "sharded sweep (empty string skips it)")
    ap.add_argument("--shard-target-s", type=float, default=1.2,
                    help="modelled 1-host transmission time for the sweep")
    ap.add_argument("--recovery-hosts", default="2,4,8",
                    help="comma-separated host counts for the partial-vs-"
                         "full recovery sweep (empty string skips it)")
    ap.add_argument("--reshard-pairs", default="2:3,4:2",
                    help="comma-separated src:tgt host-count pairs for the "
                         "elastic resharding sweep (empty string skips it)")
    # ---- remote store section ----
    ap.add_argument("--remote-error-rates", default="0.05,0.2",
                    help="seeded fault-injection error rates for the remote "
                         "sweep (empty string skips the remote section)")
    ap.add_argument("--remote-part-size", type=int, default=262_144,
                    help="multipart threshold for the remote store (small "
                         "enough that chunk puts exercise multipart)")
    # ---- restore section ----
    ap.add_argument("--restore-chain", type=int, default=3,
                    help="incremental checkpoints replayed on top of the "
                         "baseline")
    ap.add_argument("--restore-touch", type=float, default=0.25,
                    help="fraction of rows each increment touches")
    ap.add_argument("--read-mbps", type=float, default=50.0,
                    help="modelled shared-link read bandwidth (MB/s)")
    ap.add_argument("--read-latency-ms", type=float, default=20.0,
                    help="modelled per-GET first-byte latency")
    ap.add_argument("--restore-workers", type=int, default=4,
                    help="streaming-restore fetch threads")
    ap.add_argument("--decode-workers", type=int, default=2,
                    help="streaming-restore decode threads")
    ap.add_argument("--restore-repeats", type=int, default=3)
    ap.add_argument("--restore-only", action="store_true",
                    help="run only the restore section (CI gate: exits "
                         "nonzero unless restores are byte-identical)")
    ap.add_argument("--multiprocess", action="store_true",
                    help="include the real-process host sweep (OS process "
                         "per host, coordinator-less last-voter commit)")
    ap.add_argument("--mp-hosts", default="2,4",
                    help="host counts for the --multiprocess sweep")
    ap.add_argument("--multiprocess-only", action="store_true",
                    help="run only the real-process sweep (CI gate: exits "
                         "nonzero unless restores are byte-identical)")
    ap.add_argument("--serve-replicas", type=int, default=3,
                    help="subscriber replicas for the serving section "
                         "(0 skips it)")
    ap.add_argument("--serve-steps", type=int, default=4,
                    help="incremental steps each replica tracks")
    ap.add_argument("--serve-touch", type=float, default=0.05,
                    help="fraction of rows touched per serving step")
    ap.add_argument("--prior-adaptive-wall", type=float, default=1.157,
                    help="previously recorded pipelined adaptive wall_s "
                         "(the issue's 3x baseline)")
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizes")
    ap.add_argument("--out", default="BENCH_write_path.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.tiny:
        args.tables, args.rows, args.dim = 2, 8192, 32
        args.chunk_rows, args.pack_codes = 1024, 262_144
        args.shard_target_s = 0.3
        args.read_mbps, args.read_latency_ms = 20.0, 5.0
        args.restore_repeats = 1
    args.num_hosts = [int(n) for n in str(args.num_hosts).split(",") if n]
    args.recovery_hosts = [int(n) for n in
                           str(args.recovery_hosts).split(",") if n]
    args.mp_hosts = [int(n) for n in str(args.mp_hosts).split(",") if n]
    args.reshard_pairs = [tuple(int(x) for x in p.split(":"))
                          for p in str(args.reshard_pairs).split(",") if p]
    args.remote_error_rates = [float(r) for r in
                               str(args.remote_error_rates).split(",") if r]
    if args.tiny and args.multiprocess_only:
        args.mp_hosts = [2]

    qcfg = QuantConfig(bits=args.bits, method=args.method).resolve()

    if args.multiprocess_only:
        print(f"== multiprocess hosts ({args.tables}x{args.rows}x{args.dim},"
              f" hosts {args.mp_hosts}) ==")
        multiproc = bench_multiprocess(args, qcfg)
        print(json.dumps(multiproc, indent=1))
        report = {
            "bench": "write_path:multiprocess_only",
            "multiprocess": multiproc,
            "acceptance": {
                "multiprocess_restored_identical":
                    multiproc["restored_identical"],
            },
        }
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}")
        return report

    if args.restore_only:
        print(f"== chain restore ({args.tables}x{args.rows}x{args.dim}, "
              f"chain {1 + args.restore_chain}) ==")
        restore = bench_restore(args, qcfg)
        print(json.dumps(restore, indent=1))
        report = {
            "bench": "write_path:restore_only",
            "restore": restore,
            "acceptance": {
                "restore_restored_identical": restore["restored_identical"],
                "restore_speedup_ge_2_5x": restore["speedup_restore"] >= 2.5,
            },
        }
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}")
        return report

    print(f"== write-path end-to-end ({args.tables}x{args.rows}x{args.dim}, "
          f"{qcfg.bits}-bit {qcfg.method}) ==")
    e2e = bench_end_to_end(args, qcfg)
    print(json.dumps(e2e, indent=1))

    # the paper-default adaptive config: quant-search-bound on CPU — the
    # fused r-space op + per-chunk encode parallelism take this stage (on
    # TPU the fused Pallas kernel does)
    adaptive = None
    if not args.tiny and args.method != "adaptive":
        import copy
        a_args = copy.copy(args)
        print("== write-path end-to-end (4-bit adaptive, fused) ==")
        adaptive = bench_end_to_end(a_args, QuantConfig(bits=4,
                                                        method="adaptive"))
        adaptive["speedup_vs_prior_recorded"] = round(
            args.prior_adaptive_wall / adaptive["pipelined"]["wall_s"], 2)
        print(json.dumps(adaptive, indent=1))

    print(f"== chain restore (chain {1 + args.restore_chain}, "
          f"{args.read_mbps} MB/s reads, {args.read_latency_ms} ms GET) ==")
    restore = bench_restore(args, qcfg)
    print(json.dumps(restore, indent=1))

    sharded = None
    if args.num_hosts:
        print(f"== sharded multi-host sweep {args.num_hosts} "
              f"(shared vs per-host links) ==")
        sharded = bench_sharded(args, qcfg)
        print(json.dumps(sharded, indent=1))

    remote = None
    if args.remote_error_rates:
        print(f"== remote object store (faults {args.remote_error_rates}, "
              f"retry amplification + link-model bandwidth) ==")
        remote = bench_remote(args, qcfg)
        print(json.dumps(remote, indent=1))

    multiproc = None
    if args.multiprocess:
        print(f"== multiprocess hosts {args.mp_hosts} "
              f"(threads vs real OS processes) ==")
        multiproc = bench_multiprocess(args, qcfg)
        print(json.dumps(multiproc, indent=1))

    recov = None
    if args.recovery_hosts:
        print(f"== partial vs full recovery {args.recovery_hosts} "
              f"(host loss, {args.read_mbps} MB/s reads) ==")
        recov = bench_recovery(args, qcfg)
        print(json.dumps(recov, indent=1))

    reshard = None
    if args.reshard_pairs:
        print(f"== elastic resharding {args.reshard_pairs} "
              f"(N->M range reads, {args.read_mbps} MB/s reads) ==")
        reshard = bench_resharding(args, qcfg)
        print(json.dumps(reshard, indent=1))

    serving = None
    if args.serve_replicas:
        print(f"== serving fleet ({args.serve_replicas} replicas x "
              f"{args.serve_steps} steps, touch {args.serve_touch}, "
              f"{args.read_mbps} MB/s reads) ==")
        serving = bench_serving(args, qcfg)
        print(json.dumps(serving, indent=1))

    print(f"== packing microbench ({args.pack_codes} codes) ==")
    pack = bench_packing(args.pack_codes, extra_bits=args.bits)
    print(json.dumps(pack, indent=1))

    report = {
        "bench": "write_path",
        "context": {"cpu_count": os.cpu_count()},
        "end_to_end": e2e,
        "end_to_end_adaptive": adaptive,
        "restore": restore,
        "sharded": sharded,
        "remote": remote,
        "multiprocess": multiproc,
        "recovery": recov,
        "resharding": reshard,
        "serving": serving,
        "packing": pack,
        "acceptance": {
            "e2e_speedup_ge_3x": e2e["speedup_e2e"] >= 3.0,
            "pack_speedup_ge_5x": pack[f"{args.bits}bit"]["pack_speedup"] >= 5.0,
            "restored_identical": e2e["restored_identical"],
            "fused_vs_hostpack_identical": e2e["fused_vs_hostpack_identical"],
            "adaptive_encode_ge_3x_vs_recorded": (
                adaptive["speedup_vs_prior_recorded"] >= 3.0
                if adaptive else None),
            "restore_restored_identical": restore["restored_identical"],
            "restore_speedup_ge_2_5x": restore["speedup_restore"] >= 2.5,
            "sharded_restored_identical": (
                sharded["restored_identical"] if sharded else None),
            "multiprocess_restored_identical": (
                multiproc["restored_identical"] if multiproc else None),
            # per-host links must scale: 4 hosts ≥ 2× over the shared link
            "sharded_4host_speedup_ge_2x": (
                next((r["per_host_speedup"] >= 2.0 for r in sharded["sweep"]
                      if r["num_hosts"] == 4), None)
                if sharded else None),
            "remote_restored_identical": (
                remote["restored_identical"] if remote else None),
            # retries must stay bounded: at ≤20% seeded error rate the
            # wire bytes may not exceed 3x the logical payload
            "remote_amplification_le_3x": (
                all(r["write_amplification"] <= 3.0
                    for r in remote["fault_sweep"])
                if remote else None),
            # a host-loss recovery fetches ≈ the victim's shard (1/N of
            # the tables + metadata/dense overhead), not the model
            "partial_recovery_bytes_o_shard": (
                all(r["bytes_o_shard"] for r in recov["sweep"])
                if recov else None),
            "partial_recovery_matches_full_slice": (
                recov["partial_matches_full_slice"] if recov else None),
            # elastic N->M restore: each new host fetches ≈ its own
            # target shard per the range plan's estimate, and every
            # target shard equals the full restore's slice
            "resharding_bytes_o_shard": (
                all(r["bytes_o_shard"] for r in reshard["sweep"])
                if reshard else None),
            "resharding_matches_full_slice": (
                reshard["matches_full_slice"] if reshard else None),
            # a serving replica's per-step refresh fetches ≈ the touched
            # rows' payload (the delta index's own estimate), never the
            # model; every replica ends byte-identical to restore(head)
            "serving_bytes_o_touched": (
                serving["bytes_o_touched"] if serving else None),
            "serving_matches_restore": (
                serving["matches_restore"] if serving else None),
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
