"""End-to-end system behaviour: sharded training + Check-N-Run on a real
(host-device) mesh, elastic restore across meshes, and a miniature dry-run."""

import numpy as np
import pytest  # noqa: F401  (parametrize-ready; keep import stable)

# Back in the push-time fast set: the process-wide jitted-train-step cache
# (train/loop.py, PR 3) brought this module from multi-minute to ~30 s.
# The remaining slow-marked suites are test_models_smoke (40-cell sweep)
# and test_distribution (subprocess per emulated mesh).

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_cell
from repro.core import CheckpointConfig, InMemoryStore, PAPER_DEFAULTS
from repro.data.cells import batch_for_cell
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_host_mesh
from repro.train.loop import Trainer, TrainerConfig


def test_sharded_train_and_cross_mesh_restore():
    """Train on a 1×1 'mesh', checkpoint, restore into plain single-device
    state — the manifests are layout-independent (elastic restore)."""
    store = InMemoryStore()
    cfg = CheckpointConfig(interval_batches=3, policy="intermittent",
                           quant=None, async_write=False)
    b = get_cell("dlrm-rm2", "train_batch", reduced=True)
    t1 = Trainer(b, store, cfg, TrainerConfig(total_steps=3,
                                              use_reader_tier=False))
    t1.init_or_restore()
    t1.run(3)
    ref = {k: np.asarray(v) for k, v in t1.state.params["tables"].items()}
    t1.close()

    t2 = Trainer(b, store, cfg, TrainerConfig(total_steps=3,
                                              use_reader_tier=False))
    start = t2.init_or_restore()
    assert start == 3
    for k, v in t2.state.params["tables"].items():
        np.testing.assert_array_equal(np.asarray(v), ref[k])
    t2.close()


def test_mini_dryrun_lower_and_collectives():
    """A miniature of the production dry-run: lower + compile a train step
    for a 1×1 mesh and parse the collective inventory from the HLO."""
    mesh = make_host_mesh(1, 1)
    b = get_cell("bert4rec", "train_batch", mesh=mesh, reduced=True)
    state_shapes = b.state_shapes()
    sh = jax.tree.map(lambda p: NamedSharding(mesh, p if p is not None else P()),
                      b.state_pspecs(state_shapes),
                      is_leaf=lambda x: x is None or isinstance(x, P))
    in_sh = jax.tree.map(lambda p: NamedSharding(mesh, p if p is not None else P()),
                         b.input_pspecs,
                         is_leaf=lambda x: x is None or isinstance(x, P))
    with mesh:
        lowered = jax.jit(b.step_fn, in_shardings=(sh, in_sh)).lower(
            state_shapes, b.make_inputs())
        compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0
    coll = collective_bytes(compiled.as_text(), n_devices=1)
    assert "total" in coll and coll["total"] >= 0


def test_quantized_ckpt_roundtrip_through_trainer():
    b = get_cell("mind", "train_batch", reduced=True)
    store = InMemoryStore()
    cfg = CheckpointConfig(interval_batches=2, policy="one_shot",
                           quant=PAPER_DEFAULTS[8], async_write=False)
    t = Trainer(b, store, cfg, TrainerConfig(total_steps=4,
                                             use_reader_tier=False))
    t.init_or_restore()
    t.run(4)
    live = np.asarray(t.state.params["tables"]["item_0"])
    t.close()
    t2 = Trainer(b, store, cfg, TrainerConfig(total_steps=4,
                                              use_reader_tier=False))
    t2.init_or_restore()
    rest = np.asarray(t2.state.params["tables"]["item_0"])
    # 8-bit quantization: close but not equal
    assert np.abs(live - rest).max() < 0.05
    assert not np.array_equal(live, rest)
    t2.close()
