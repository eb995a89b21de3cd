"""End-to-end recovery tests: the paper's central correctness claims.

* fp32 checkpoints → restored training trajectory is EXACTLY the
  uninterrupted one (same batches via reader-state, same params bit-for-bit).
* quantized checkpoints → bounded parameter perturbation, training proceeds.
* reader-trainer gap: restored run consumes exactly the remaining stream.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_cell
from repro.core import CheckNRunManager, CheckpointConfig, InMemoryStore, PAPER_DEFAULTS
from repro.data.cells import batch_for_cell
from repro.train.loop import SimulatedFailure, Trainer, TrainerConfig

# Back in the fast push-time set: Trainers share one compiled train step
# per cell (train.loop._jitted_step) and the runs are trimmed to the
# shortest schedules that still cross a checkpoint + failure + recovery.


_CELLS = {}


def get_cell_cached(arch):
    """One bundle per arch for the whole module: every test's Trainers then
    share one compiled train step via train.loop._jitted_step."""
    if arch not in _CELLS:
        _CELLS[arch] = get_cell(arch, "train_batch", reduced=True)
    return _CELLS[arch]


def flat_params(state):
    leaves = jax.tree_util.tree_flatten_with_path(state.params)[0]
    return {jax.tree_util.keystr(p): np.asarray(jax.device_get(l))
            for p, l in leaves}


@pytest.mark.parametrize("arch", ["dlrm-rm2", "bert4rec"])
def test_failure_recovery_bitwise_equal(arch):
    """Kill at step 5, restore from the step-3 checkpoint, retrain → params
    identical to an uninterrupted 6-step run."""
    bundle = get_cell_cached(arch)

    # uninterrupted reference run
    ref_store = InMemoryStore()
    t_ref = Trainer(bundle, ref_store,
                    CheckpointConfig(interval_batches=3, policy="intermittent",
                                     quant=None, async_write=False),
                    TrainerConfig(total_steps=6, use_reader_tier=True))
    t_ref.init_or_restore()
    ref_state = t_ref.run(6)
    t_ref.close()

    # failing run on its own store
    store = InMemoryStore()
    cfg = CheckpointConfig(interval_batches=3, policy="intermittent",
                           quant=None, async_write=False)
    t1 = Trainer(bundle, store, cfg, TrainerConfig(total_steps=6))
    t1.init_or_restore()
    with pytest.raises(SimulatedFailure):
        t1.run(6, fail_at_step=5)
    t1.close()

    # recovery: restore from checkpoint@3, train to 6
    t2 = Trainer(bundle, store, cfg, TrainerConfig(total_steps=6))
    start = t2.init_or_restore()
    assert start == 3
    final = t2.run(3)
    t2.close()

    a, b = flat_params(ref_state), flat_params(final)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_quantized_recovery_bounded_and_trains():
    """Restore from a 4-bit checkpoint: params must differ from the fp32
    checkpoint state only by the quantization error (compare against an
    fp32-checkpoint twin run at the SAME restore step — no training drift),
    and training must continue to finite losses."""
    bundle = get_cell_cached("dlrm-rm2")

    def run_and_restore(quant):
        store = InMemoryStore()
        cfg = CheckpointConfig(interval_batches=3, policy="intermittent",
                               quant=quant, async_write=False)
        t1 = Trainer(bundle, store, cfg, TrainerConfig(total_steps=6))
        t1.init_or_restore()
        with pytest.raises(SimulatedFailure):
            t1.run(6, fail_at_step=5)
        t1.close()
        t2 = Trainer(bundle, store, cfg, TrainerConfig(total_steps=6))
        assert t2.init_or_restore() == 3
        return t2

    tq = run_and_restore(PAPER_DEFAULTS[4])
    tf = run_and_restore(None)
    a, b = flat_params(tf.state), flat_params(tq.state)
    rel_mean = max(np.abs(a[k] - b[k]).mean() / (np.abs(a[k]).mean() + 1e-9)
                   for k in a)
    assert 0 < rel_mean < 0.1   # pure quantization delta, small but nonzero
    final = tq.run(3)
    tq.close()
    tf.close()
    assert np.isfinite(float(jax.device_get(final.step)))


def test_trainer_stall_fraction_small():
    """§3.2: snapshot stall is a tiny fraction of train time (decoupling).
    The snapshot stall is the drain of the dispatched steps plus the
    device→host copy (the cnr.snapshot.* spans); ``stall_times`` times the
    whole ``checkpoint()``, the non-overlap wait for the previous save
    included."""
    from repro.core import trace

    bundle = get_cell_cached("dlrm-rm2")
    store = InMemoryStore()
    t = Trainer(bundle, store,
                CheckpointConfig(interval_batches=3, policy="intermittent",
                                 quant=PAPER_DEFAULTS[4], async_write=True),
                TrainerConfig(total_steps=6))
    t.init_or_restore()
    import time
    trace.drain()
    t0 = time.monotonic()
    with trace.record():
        t.run(6)
    total = time.monotonic() - t0
    t.manager.wait()
    t.close()
    spans = trace.drain()
    snapshot = sum(s.seconds for s in spans
                   if s.name in ("cnr.snapshot.drain", "cnr.snapshot.copy"))
    assert len(t.stall_times) == 2
    assert t.stall_times == [s.seconds for s in spans
                             if s.name == "cnr.checkpoint"]
    assert snapshot < 0.5 * total  # generous bound for CPU CI


def test_touched_masks_reset_after_checkpoint():
    bundle = get_cell_cached("dlrm-rm2")
    store = InMemoryStore()
    t = Trainer(bundle, store,
                CheckpointConfig(interval_batches=3, policy="one_shot",
                                 quant=None, async_write=False),
                TrainerConfig(total_steps=3))
    t.init_or_restore()
    t.run(3)
    # after the step-3 checkpoint the on-device masks are zeroed
    assert all(int(np.asarray(v).sum()) == 0 for v in t.state.touched.values())
    t.close()
