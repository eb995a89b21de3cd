#!/usr/bin/env python3
"""On-chip smoke run of Check-N-Run's main path: DLRM-RM2 training with
incremental quantized checkpoints, restore and resume, on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the 2x2-mesh phase only (4 chips)

One process drives the chip through the entry points a user calls:
``Trainer`` + ``CheckNRunManager`` over a ``LocalFSStore``. The model is
DLRM-RM2 at its published widths (13 dense features, 26 sparse fields at
dim 64, bottom MLP 13-512-256-64, top MLP 512-512-256-1, batch 65,536,
the sparse train step). The one cut: every Criteo-Terabyte cardinality is
capped at 2,000,000 rows, as DLRM's hashing trick caps them, so the f32
tables and their row-wise AdaGrad state fit one 16 GB chip. Weights and
data come from fixed seeds.

Single-chip phases (each raises on failure; nothing is caught):

1. build the capped model and print its size;
2. train 6 steps with ``interval_batches=2``, 4-bit adaptive quantization
   and the intermittent policy — one full save, then incremental saves —
   and check that the fused Pallas quantize+pack kernel and the Pallas
   chunk hash ran, that their payloads equal the jnp device path's byte
   for byte and the stored bytes, and that every recorded ``hash32``
   equals the host oracle over the written bytes;
3. restore into a fresh ``Trainer``, check dense and optimizer state
   against the saved snapshot and the tables against an independent
   replay of the dequantized payloads, then train 2 more steps;
4. print the device, compile, save and memory readings.

``--four-chips`` instead places the same capped state on a 2x2 (data,
model) mesh, checks one sharded step's loss against the single-device
step, and checks that saving the sharded state restores byte-identically
to a save of the same state from one device.

The last line of standard output is a JSON object: ``{"ok": true,
"device": {"platform", "kind", "count"}}``. Without a TPU the script
prints no result and exits non-zero. Stall, compile and memory figures
are bring-up readings, not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, ".chip_smoke")

VOCAB_CAP = 2_000_000     # rows per table, as DLRM's hashing trick caps them
TRAIN_STEPS = 6
INTERVAL = 2
RESUME_STEPS = 2
BITS = 4                  # PAPER_DEFAULTS[4]: 4-bit adaptive
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compilations seen by ``jax.monitoring``: (name, seconds)."""

    def __init__(self) -> None:
        self.events = []

    def __call__(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            name = kw.get("fun_name", "?")
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.events.append((name, duration))

    def count(self, name: str, since: int = 0) -> int:
        return sum(1 for n, _ in self.events[since:] if n == name)

    def seconds(self, name=None, since: int = 0) -> float:
        return sum(d for n, d in self.events[since:]
                   if name is None or n == name)


# ------------------------------------------------------------------ model
def capped_bundle(mesh=None, reduced: bool = False):
    """DLRM-RM2 cell with each vocabulary capped at ``VOCAB_CAP`` rows
    (rounded up to the 512-row shard multiple); ``reduced`` gives the
    registry's CPU-sized config for rehearsals."""
    from repro.configs._families import recsys_cell
    from repro.configs.dlrm_rm2 import make_config
    from repro.models.embedding import pad_rows

    cfg = make_config(reduced)
    if not reduced:
        cfg = dataclasses.replace(cfg, vocab_sizes=tuple(
            pad_rows(min(v, VOCAB_CAP)) for v in cfg.vocab_sizes))
    return recsys_cell("dlrm-rm2", cfg, "train_batch", mesh, reduced)


def describe(bundle) -> None:
    from repro.configs.dlrm_rm2 import CRITEO_TB_VOCABS

    cfg = bundle.cfg
    rows = sum(cfg.vocab_sizes)
    full_rows = sum(CRITEO_TB_VOCABS)
    table_b = rows * cfg.embed_dim * 4
    log(f"model: {cfg.name} dense={cfg.n_dense} sparse={cfg.n_sparse} "
        f"dim={cfg.embed_dim} bot={(cfg.n_dense,) + cfg.bot_mlp} "
        f"top={cfg.top_mlp} batch={bundle.make_inputs()['label'].shape[0]}")
    log(f"vocab cap: {VOCAB_CAP} rows/table -> {rows} rows "
        f"({rows / full_rows:.4f} of the uncapped {full_rows}); "
        f"f32 tables {table_b / 1e9:.3f} GB + row-wise AdaGrad "
        f"{rows * 4 / 1e9:.3f} GB (uncapped: "
        f"{full_rows * cfg.embed_dim * 4 / 1e9:.1f} GB)")


def _ckpt_config(quant_impl: str):
    from repro.core import CheckpointConfig, PAPER_DEFAULTS

    # keep every save's manifest so each one can be checked and reported
    return CheckpointConfig(interval_batches=INTERVAL, policy="intermittent",
                            quant=PAPER_DEFAULTS[BITS], quant_impl=quant_impl,
                            keep_latest=TRAIN_STEPS // INTERVAL)


def _section(data: bytes, ch, name: str) -> bytes:
    o, n = ch.sections[name]
    return data[o:o + n]


def _chunk_rows(snap_table, ch, data):
    import numpy as np

    if "indices" in ch.sections:
        idx = np.frombuffer(_section(data, ch, "indices"), np.uint32)
        return idx.astype(np.int64), snap_table[idx]
    lo, hi = ch.row_range
    return np.arange(lo, hi), snap_table[lo:hi]


def _dequantized(rec, ch, data):
    """The reference decode of one chunk's payload: ``unpack_bits`` +
    ``dequantize`` over the fp16 metadata, nothing from the restore
    pipeline."""
    import numpy as np

    from repro.core import packing
    from repro.core.quantize import Quantized, dequantize

    meta = np.dtype(rec.meta_dtype)
    scale = np.frombuffer(_section(data, ch, "scale"), meta).astype(np.float32)
    zero = np.frombuffer(_section(data, ch, "zero"), meta).astype(np.float32)
    codes = packing.unpack_bits(_section(data, ch, "codes"), rec.bits,
                                ch.n_rows * rec.dim)
    q = Quantized(codes.reshape(-1, rec.dim), scale, zero, bits=rec.bits)
    return np.asarray(dequantize(q))


# ------------------------------------------------------- single-chip run
def check_payloads(store, man, snap, picks, quant_impl: str) -> None:
    """For the picked chunks of one save: the fused Pallas payload equals
    the jnp device path's and the stored bytes, byte for byte, and so do
    the per-row scale/zero."""
    import numpy as np

    from repro.core import packing
    from repro.core.checkpoint import META_DTYPE
    from repro.kernels.adaptive_quant import quant_pack

    q = man.quant
    for name, seq in picks:
        rec = man.tables[name]
        ch = rec.chunks[seq]
        data = store.get(ch.key)
        _, rows = _chunk_rows(snap.tables[name], ch, data)
        kw = dict(bits=q["bits"], method=q["method"],
                  num_bins=q["num_bins"], ratio=q["ratio"])
        pk = quant_pack(rows, impl=quant_impl, **kw)
        pj = quant_pack(rows, impl="jnp", **kw)
        bk = packing.words_to_payload(np.asarray(pk.words), pk.count,
                                      q["bits"])
        bj = packing.words_to_payload(np.asarray(pj.words), pj.count,
                                      q["bits"])
        assert bk == bj, f"{ch.key}: Pallas and jnp payloads differ"
        assert bk == _section(data, ch, "codes"), \
            f"{ch.key}: stored codes differ from the Pallas payload"
        for a, b, nm in ((pk.scale, pj.scale, "scale"),
                         (pk.zero, pj.zero, "zero")):
            assert a.tobytes() == b.tobytes(), f"{ch.key}: {nm} differs"
            assert (np.asarray(a, META_DTYPE).tobytes()
                    == _section(data, ch, nm)), f"{ch.key}: stored {nm}"
        log(f"  payload {ch.key}: {ch.n_rows} rows, {len(bk)} B — "
            f"Pallas == jnp == stored, byte for byte")


def check_hashes(store) -> int:
    """Every recorded chunk hash32 equals the host oracle over the
    written bytes of its primary section."""
    from repro.core import manifest as mf
    from repro.core.integrity import primary_section
    from repro.kernels.chunk_hash.ref import chunk_hash32

    n = 0
    for step in mf.list_steps(store):
        for rec in mf.load(store, step).tables.values():
            for ch in rec.chunks:
                data = store.get(ch.key)
                want = chunk_hash32(_section(data, ch, primary_section(ch)))
                assert ch.hash32 is not None and ch.hash32 == want, ch.key
                n += 1
    return n


def replay_tables(store, step: int):
    """Independent chain replay: full-save payloads, then each increment
    overwrites its rows — every value from :func:`_dequantized`."""
    import numpy as np

    from repro.core import manifest as mf

    out = {}
    for man in mf.recovery_chain(store, step):
        for name, rec in man.tables.items():
            tab = out.setdefault(name, np.zeros((rec.rows, rec.dim),
                                                np.float32))
            for ch in rec.chunks:
                data = store.get(ch.key)
                if "indices" in ch.sections:
                    idx = np.frombuffer(_section(data, ch, "indices"),
                                        np.uint32).astype(np.int64)
                else:
                    idx = np.arange(*ch.row_range)
                tab[idx] = _dequantized(rec, ch, data)
    return out


def run_single(reduced: bool = False, quant_impl: str = "auto") -> None:
    import jax
    import numpy as np

    from repro.core import LocalFSStore
    from repro.core import manifest as mf
    from repro.kernels.adaptive_quant.kernel import quant_pack_pallas
    from repro.kernels.adaptive_quant.ops import _bucket_rows, _resolve_steps
    from repro.train.loop import Trainer, TrainerConfig
    from repro.train.state import state_to_snapshot

    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    dev = jax.devices()[0]

    # ---- 1. model
    bundle = capped_bundle(reduced=reduced)
    describe(bundle)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    store = LocalFSStore(os.path.join(OUT_DIR, "store"))
    ckpt = _ckpt_config(quant_impl)

    # ---- 2. train and save
    trainer = Trainer(bundle, store, ckpt,
                      TrainerConfig(total_steps=TRAIN_STEPS, log_every=1))
    t0 = time.monotonic()
    assert trainer.init_or_restore() == 0
    jax.block_until_ready(trainer.state)
    init_s = time.monotonic() - t0
    t0 = time.monotonic()
    trainer.run(1)
    jax.block_until_ready(trainer.state)
    first_step_s = time.monotonic() - t0
    step_compile_s = compiles.seconds("train_step")
    mark = len(compiles.events)
    trainer.run(INTERVAL - 1)           # step 2 checkpoints: the full save
    snap_full = trainer._boundary_snaps[INTERVAL]
    trainer.run(TRAIN_STEPS - INTERVAL)
    trainer.manager.wait()
    n_qp = compiles.count("quant_pack_pallas", mark)
    n_hash = compiles.count("chunk_hash_pallas", mark)
    losses = [h["loss"] for h in trainer.history]
    assert len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)), \
        losses
    snap_last = trainer._boundary_snaps[TRAIN_STEPS]
    steps = mf.list_steps(store)
    assert steps == list(range(INTERVAL, TRAIN_STEPS + 1, INTERVAL)), steps
    stalls = dict(zip(steps, trainer.stall_times, strict=True))
    mans = {s: mf.load(store, s) for s in steps}
    kinds = [mans[s].kind for s in steps]
    assert kinds[0] == "full" and "incremental" in kinds, kinds

    # the Pallas kernels ran: both compiled while saving, and "auto"
    # resolves to them on this backend
    assert n_qp >= 1 and n_hash >= 1, (n_qp, n_hash)
    num_bins, n_steps = _resolve_steps("adaptive", BITS, None, None)
    hlo = quant_pack_pallas.lower(
        np.zeros((_bucket_rows(1), bundle.cfg.embed_dim), np.float32),
        bits=BITS, num_bins=num_bins, n_steps=n_steps,
        interpret=quant_impl == "interpret").as_text()
    if quant_impl == "auto":
        assert "tpu_custom_call" in hlo
    log(f"train: losses {['%.6f' % v for v in losses]}")

    # Pallas == jnp == stored bytes on a full chunk, the ragged tail of
    # the largest table, and the largest increment chunk
    full = mans[steps[0]]
    big = max(full.tables, key=lambda n: full.tables[n].rows)
    last = mans[TRAIN_STEPS]
    inc_name = max(last.tables, key=lambda n: last.tables[n].chunks[0].n_rows
                   if last.tables[n].chunks else 0)
    check_payloads(store, full, snap_full,
                   [(big, 0), (big, len(full.tables[big].chunks) - 1)],
                   quant_impl)
    check_payloads(store, last, snap_last, [(inc_name, 0)], quant_impl)
    n_hashed = check_hashes(store)
    log(f"  hash32 == host chunk_hash32 over the written bytes: "
        f"{n_hashed} chunks")
    peak_train = dev.memory_stats() or {}
    peak_train = peak_train.get("peak_bytes_in_use")

    # ---- 3. restore and resume
    trainer.state = None
    trainer.close()
    del trainer
    t2 = Trainer(bundle, store, ckpt,
                 TrainerConfig(total_steps=RESUME_STEPS, log_every=1))
    t0 = time.monotonic()
    start = t2.init_or_restore()
    jax.block_until_ready(t2.state)
    restore_s = time.monotonic() - t0
    assert start == TRAIN_STEPS, start
    got = state_to_snapshot(t2.state, bundle.tracked, {})
    for k, v in snap_last.dense.items():
        assert np.asarray(got.dense[k]).tobytes() == v.tobytes(), k
    for name, aux in snap_last.row_state.items():
        for a, v in aux.items():
            assert got.row_state[name][a].tobytes() == v.tobytes(), (name, a)
    oracle = replay_tables(store, TRAIN_STEPS)
    for name, tab in oracle.items():
        assert got.tables[name].tobytes() == tab.tobytes(), name
    log(f"restore: step {start}; {len(snap_last.dense)} dense leaves and "
        f"the row-wise AdaGrad state equal the saved snapshot; "
        f"{len(oracle)} tables equal the replayed dequantized payloads")
    del got, oracle, snap_full, snap_last
    t2.run(RESUME_STEPS)
    t2.manager.wait()
    resumed = [h["loss"] for h in t2.history]
    assert len(resumed) == RESUME_STEPS and all(map(math.isfinite, resumed))
    log(f"resume: steps {start + 1}..{start + RESUME_STEPS} losses "
        f"{['%.6f' % v for v in resumed]}")
    t2.close()

    # ---- 4. report
    stats = dev.memory_stats() or {}
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    log("bring-up readings (one run, not benchmark results):")
    log(f"  state init {init_s:.3f} s; first step {first_step_s:.3f} s, "
        f"of which train_step backend compile {step_compile_s:.3f} s")
    log(f"  backend compiles: {len(compiles.events)} programs, "
        f"{compiles.seconds():.3f} s in all")
    log(f"  quant_pack_pallas compilations during saves: {n_qp}; "
        f"chunk_hash_pallas: {n_hash}")
    for s in steps:
        m = mans[s]
        log(f"  save step {s}: {m.kind}, {m.nbytes_total} B, snapshot "
            f"stall {stalls[s]:.3f} s, save wall {m.wall_time_s:.3f} s")
    log(f"  restore (init_or_restore) {restore_s:.3f} s")
    log(f"  peak_bytes_in_use: {peak_train} after training+saves, "
        f"{stats.get('peak_bytes_in_use')} at the end")
    shutil.rmtree(OUT_DIR, ignore_errors=True)


# ------------------------------------------------------------ four chips
def run_four_chips(reduced: bool = False, quant_impl: str = "auto") -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import LocalFSStore, CheckNRunManager
    from repro.data.cells import batch_for_cell
    from repro.launch.mesh import make_host_mesh
    from repro.train.loop import Trainer
    from repro.train.state import restore_train_state

    assert len(jax.devices()) >= 4, jax.devices()
    mesh = make_host_mesh(2, 2)
    b1 = capped_bundle(reduced=reduced)
    bm = capped_bundle(mesh=mesh, reduced=reduced)
    describe(b1)
    shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, p if p is not None else P()),
        bm.state_pspecs(), is_leaf=lambda x: x is None or isinstance(x, P))
    batch = batch_for_cell(b1, 0)

    state = b1.make_state()
    sharded = jax.device_put(state, shardings)
    s1, m1 = jax.jit(b1.step_fn)(state, batch)
    loss1 = float(m1["loss"])
    emb1 = np.asarray(s1.params["tables"]["emb_0"])
    del state, s1
    s2, m2 = jax.jit(bm.step_fn)(sharded, batch)
    loss2 = float(m2["loss"])
    emb2 = np.asarray(jax.device_get(s2.params["tables"]["emb_0"]))
    del sharded
    log(f"sharded step on {dict(mesh.shape)}: loss {loss2:.7f} vs single-device "
        f"{loss1:.7f} (|diff| {abs(loss1 - loss2):.3e})")
    assert abs(loss1 - loss2) < 1e-4, (loss1, loss2)
    np.testing.assert_allclose(emb1, emb2, rtol=1e-3, atol=1e-5)
    del emb1, emb2

    # save the sharded state, and the same state from one device
    stores = {}
    for label, st in (("sharded", s2),
                      ("one-device", jax.device_put(s2, jax.devices()[0]))):
        store = LocalFSStore(os.path.join(OUT_DIR, label))
        tr = Trainer(bm if label == "sharded" else b1, store,
                     _ckpt_config(quant_impl))
        tr.state = st
        tr.checkpoint()
        tr.manager.wait()
        tr.state = None
        tr.close()
        stores[label] = store
        del st
    del s2
    keys = sorted(stores["sharded"].list("chunks/"))
    assert keys == sorted(stores["one-device"].list("chunks/")) and keys
    for k in keys:
        assert stores["sharded"].get(k) == stores["one-device"].get(k), k
    rs = {label: CheckNRunManager(store, _ckpt_config(quant_impl)).restore()
          for label, store in stores.items()}
    a, b = rs["sharded"], rs["one-device"]
    for name in a.tables:
        assert a.tables[name].tobytes() == b.tables[name].tobytes(), name
        for aux in a.row_state[name]:
            assert (a.row_state[name][aux].tobytes()
                    == b.row_state[name][aux].tobytes()), (name, aux)
    for k in a.dense:
        assert a.dense[k].tobytes() == b.dense[k].tobytes(), k
    # and the restore lands on the mesh with the same bytes
    placed = restore_train_state(bm.make_state(), a, bm.tracked, shardings)
    for name, spec in bm.tracked.items():
        arr = placed.params[spec.path[0]][spec.path[1]]
        assert isinstance(arr.sharding, NamedSharding), arr.sharding
        got = np.asarray(jax.device_get(arr)).reshape(spec.rows, spec.dim)
        assert got.tobytes() == b.tables[name].tobytes(), name
    log(f"sharded save: {len(keys)} chunk blobs byte-identical to the "
        f"one-device save; restores byte-identical, also when placed back "
        f"on the mesh")
    shutil.rmtree(OUT_DIR, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh phase (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU found (JAX sees {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        run_four_chips()
    else:
        run_single()
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
