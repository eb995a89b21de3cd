"""Training loop with integrated Check-N-Run checkpointing.

Wires together: the reader tier (exact-N lease protocol), the jitted train
step (touched-mask tracking inside), the snapshot adapter, and the
CheckNRunManager (async incremental+quantized checkpoints). Also provides
failure injection for the recovery tests/examples.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import trace
from ..core.bitwidth import BitwidthController
from ..core.checkpoint import CheckNRunManager, CheckpointConfig
from ..core.reader_protocol import ReaderLease
from ..core.storage import ObjectStore
from ..data.reader import DataReader
from ..train.state import (
    TrainState,
    restore_train_state,
    splice_shard_state,
    state_to_snapshot,
)
from ..train.steps import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    use_reader_tier: bool = True


@functools.lru_cache(maxsize=32)
def _jitted_step(step_fn):
    """Process-wide jit cache keyed on the bundle's step callable: every
    Trainer over the same cell reuses ONE compiled train step instead of
    re-tracing per instance (the recovery tests spin up 3-4 Trainers per
    cell — this is most of their former multi-minute wall time). Bounded so
    a long-lived sweep constructing many distinct bundles doesn't retain
    every compiled executable forever."""
    return jax.jit(step_fn, donate_argnums=(0,))


class Trainer:
    def __init__(self, bundle, store: ObjectStore, ckpt_cfg: CheckpointConfig,
                 trainer_cfg: Optional[TrainerConfig] = None,
                 batch_fn: Optional[Callable[[int], Dict[str, np.ndarray]]] = None,
                 bitwidth: Optional[BitwidthController] = None):
        from ..data.cells import batch_for_cell

        self.bundle = bundle
        self.cfg = trainer_cfg or TrainerConfig()
        self.ckpt_cfg = ckpt_cfg
        self.manager = CheckNRunManager(store, ckpt_cfg, bitwidth=bitwidth)
        self.batch_fn = batch_fn or (lambda i: batch_for_cell(bundle, i))
        self.lease = ReaderLease(ckpt_cfg.interval_batches)
        self.reader: Optional[DataReader] = None
        self.step_fn = _jitted_step(bundle.step_fn)
        self.state: Optional[TrainState] = None
        self.history: List[Dict[str, float]] = []
        self.stall_times: List[float] = []
        # last 2 checkpoint-boundary snapshots, keyed by step — host-side
        # arrays (take_snapshot copies off-device, so they survive buffer
        # donation by the jitted step). Exact-mode partial recovery rolls
        # SURVIVORS back from these for free: zero bytes fetched, only the
        # failed shard is replayed from the store.
        self._boundary_snaps: Dict[int, Any] = {}
        # restore provenance to stamp into the next save's manifest extra
        # ("degraded_from"): set when a restore/recovery fell back past the
        # step we asked for, so `ckpt show` can surface the lineage gap
        self._provenance: Optional[Dict[str, Any]] = None
        self.last_recovery: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------ lifecycle
    def init_or_restore(self) -> int:
        """Restore from the latest valid checkpoint if one exists."""
        template = self.bundle.make_state()
        try:
            restored = self.manager.restore()
        except FileNotFoundError:
            self.state = template
            start_batch = 0
        else:
            with trace.span("cnr.restore.place",
                            request=restored.request,
                            bytes=restored.nbytes()):
                self.state = restore_train_state(template, restored,
                                                 self.bundle.tracked)
                jax.block_until_ready(self.state)
            start_batch = restored.extra.get("reader", {}).get("next_batch",
                                                               int(restored.step))
            if restored.degraded_from is not None:
                self._provenance = {
                    "requested_step": restored.degraded_from,
                    "restored_step": int(restored.step),
                    "reason": "corrupt-chain fallback"}
        if self.cfg.use_reader_tier:
            from ..core.reader_protocol import ReaderState
            self.reader = DataReader(
                self.batch_fn, lease=self.lease,
                state=ReaderState(next_batch=start_batch))
            self.lease.set_limit(start_batch + self.ckpt_cfg.interval_batches)
        return start_batch

    def _next_batch(self, i: int):
        if self.reader is not None:
            return self.reader.next()
        return self.batch_fn(i)

    # ------------------------------------------------------------- training
    def run(self, n_steps: Optional[int] = None,
            fail_at_step: Optional[int] = None) -> TrainState:
        """Train; optionally raise a simulated failure at a given step."""
        n_steps = n_steps or self.cfg.total_steps
        start = int(jax.device_get(self.state.step))
        interval = self.ckpt_cfg.interval_batches
        for i in range(start, start + n_steps):
            if fail_at_step is not None and i == fail_at_step:
                raise SimulatedFailure(f"injected failure at step {i}")
            batch = self._next_batch(i)
            self.state, metrics = self.step_fn(self.state, batch)
            if (i + 1) % interval == 0:
                self.checkpoint()
            if (i + 1) % self.cfg.log_every == 0:
                m = {k: float(jax.device_get(v)) for k, v in metrics.items()
                     if jnp.ndim(v) == 0}
                m["step"] = i + 1
                self.history.append(m)
        return self.state

    def checkpoint(self) -> None:
        """§3.4 workflow: stall→snapshot, resume, optimize+store in background.
        The stall is the ``cnr.checkpoint`` span (``stall_times``): the
        drain of the dispatched steps, the copy, the release of the oldest
        boundary snapshot, and the non-overlap wait for the previous
        save."""
        with trace.span("cnr.checkpoint") as sp:
            self._checkpoint(sp)
        self.stall_times.append(sp.seconds)

    def _checkpoint(self, sp: trace.Span) -> None:
        extra = {}
        if self.reader is not None:
            # reader has delivered exactly `interval` batches — no in-flight gap
            assert self.reader.in_flight() == 0, "reader-trainer gap!"
            extra["reader"] = self.reader.checkpoint_state().to_dict()
        if self._provenance is not None:
            extra["degraded_from"] = self._provenance
            self._provenance = None
        snap = state_to_snapshot(self.state, self.bundle.tracked, extra)
        sp.set(request=snap.step, step=snap.step)
        # retain the two most recent boundary snapshots for exact-mode
        # partial recovery (the previous boundary matters when the save at
        # THIS boundary is the one that dies uncommitted)
        self._boundary_snaps[snap.step] = snap
        # dropping the oldest snapshot frees its host arrays (a whole
        # state's worth of pages) on this thread
        with trace.span("cnr.snapshot.release"):
            for s in sorted(self._boundary_snaps)[:-2]:
                del self._boundary_snaps[s]
        # training may continue: reset the on-device touched masks and renew
        # the reader lease for the next interval
        self.state = TrainState(
            step=self.state.step, params=self.state.params,
            opt_state=self.state.opt_state,
            touched={k: jnp.zeros_like(v) for k, v in self.state.touched.items()},
            rng=self.state.rng)
        if self.reader is not None:
            self.lease.renew()
        fut = self.manager.save(snap)
        if not self.ckpt_cfg.async_write:
            # synchronous saves park their exception in the returned
            # future; surface it HERE (at the boundary that failed) rather
            # than from the next interval's non-overlap wait — the partial
            # recovery path keys off which save raised
            fut.result()

    # ------------------------------------------------------ partial recovery
    def _reset_reader(self, start_batch: int) -> None:
        """Rebuild the reader tier at a rolled-back batch cursor (the old
        lease/reader pair may be mid-interval and cannot be rewound)."""
        if not self.cfg.use_reader_tier:
            return
        from ..core.reader_protocol import ReaderState

        if self.reader is not None:
            self.reader.close()
        self.lease = ReaderLease(self.ckpt_cfg.interval_batches)
        self.reader = DataReader(self.batch_fn, lease=self.lease,
                                 state=ReaderState(next_batch=start_batch))
        self.lease.set_limit(start_batch + self.ckpt_cfg.interval_batches)

    def recover_host(self, host: int, mode: str = "exact",
                     step: Optional[int] = None,
                     supervisor=None,
                     num_hosts: Optional[int] = None) -> int:
        """Recover from the loss of ONE host's shard without restarting the
        survivors (docs/partial_recovery.md). Replays only that host's
        shard chain from the committed checkpoint, splices it into a
        rebuilt/live TrainState, re-fences touched + optimizer bookkeeping
        for the shard, and resets the reader tier. Returns the step
        training resumes from.

        Staleness policy:

        * ``exact`` — survivors ALSO roll back to the committed step, from
          the retained in-memory boundary snapshot (zero store bytes);
          the resumed run is bit-identical to a never-failed run when the
          checkpoint is unquantized. Falls back to a full restore when the
          boundary snapshot is not retained (e.g. a fresh process).
        * ``cpr`` — survivors keep their LIVE state; only the failed
          shard's rows are overwritten with the committed (stale) values,
          per CPR's partial-staleness model. Training resumes from the
          live step with no lost work on survivors.

        Either way, an unrecoverable shard degrades to a full
        ``restore()`` (kind == "full" in ``last_recovery``) — everything
        rolls back and the degradation is stamped into the next save's
        manifest as ``degraded_from``.

        ``num_hosts`` recovers the host's shard under a NEW layout
        (docs/resharding.md): a trainer restarted at N±k hosts — whose
        own ``ckpt_cfg.num_hosts`` already names the new layout — can
        default it, since the range planner reads the chain regardless of
        the layout it was written under; pass it explicitly to recover a
        shard of a layout differing from the trainer's config.
        """
        from ..core import manifest as mf
        from ..dist.recovery import RecoverySupervisor

        if mode not in ("exact", "cpr"):
            raise ValueError(f"unknown staleness mode {mode!r}")
        tgt = num_hosts if num_hosts is not None \
            else (self.ckpt_cfg.num_hosts
                  if self.ckpt_cfg.num_hosts > 1 else None)
        sup = supervisor or RecoverySupervisor(
            self.manager.store, tgt or self.ckpt_cfg.num_hosts)
        committed = step if step is not None \
            else mf.latest_step(self.manager.store)
        if committed is None:
            raise FileNotFoundError("no committed checkpoint to recover from")
        rs = sup.recover(self.manager, host, step=committed, num_hosts=tgt)
        info = dict(rs.extra.get("recovery", {}))
        info["mode"] = mode
        template = self.bundle.make_state()

        if info.get("kind") == "full":
            # shard chain unrecoverable — O(model) fallback; restore()
            # already resynced the manager's policy + masks
            self.state = restore_train_state(template, rs,
                                             self.bundle.tracked)
            self._provenance = {
                "requested_host": host,
                "restored_step": int(rs.step),
                "reason": rs.extra.get("recovery_fallback_reason",
                                       "full-restore fallback")}
            self._reset_reader(rs.extra.get("reader", {})
                               .get("next_batch", int(rs.step)))
            self.last_recovery = info
            return int(rs.step)

        ranges = rs.extra["shard"]["row_range"]
        if mode == "cpr":
            self.state = splice_shard_state(self.state, rs,
                                            self.bundle.tracked)
            self.manager.refence_shard(ranges)
            self.last_recovery = info
            return int(jax.device_get(self.state.step))

        # exact: rebuild survivors from the retained boundary snapshot
        # (already host-side arrays at exactly the committed step), then
        # splice the failed shard from what the store replayed
        base = self._boundary_snaps.get(int(rs.step))
        if base is None:
            full = self.manager.restore(int(rs.step),
                                        on_corruption="fallback")
            self.manager._count(recoveries_full_total=1,
                                last_recovery_host=host)
            info["kind"] = "full"
            self.state = restore_train_state(template, full,
                                             self.bundle.tracked)
            self._reset_reader(full.extra.get("reader", {})
                               .get("next_batch", int(full.step)))
            self.last_recovery = info
            return int(full.step)
        self.state = restore_train_state(template, _SnapshotRestored(base),
                                         self.bundle.tracked)
        self.state = splice_shard_state(self.state, rs, self.bundle.tracked)
        self.manager.resync_from(int(rs.step))
        self._reset_reader(base.extra.get("reader", {})
                           .get("next_batch", int(rs.step)))
        self.last_recovery = info
        return int(rs.step)

    def close(self) -> None:
        if self.reader is not None:
            self.reader.close()
        self.manager.close()


class _SnapshotRestored:
    """Adapter presenting a boundary Snapshot through the RestoredState
    attributes ``restore_train_state`` reads (tables / row_state / dense /
    step) — the snapshot's dense dict already carries "step" and "rng"."""

    def __init__(self, snap) -> None:
        self.step = snap.step
        self.tables = snap.tables
        self.row_state = snap.row_state
        self.dense = snap.dense
        self.extra = snap.extra
        self.degraded_from = None


class SimulatedFailure(RuntimeError):
    pass
