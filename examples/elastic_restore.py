"""Elastic scaling: checkpoint under one device layout, restore under
another. Check-N-Run manifests store global row ranges, so the loader can
re-shard to any mesh — here 8 host devices → 4, mid-run.

  PYTHONPATH=src python examples/elastic_restore.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_cell
from repro.core import CheckpointConfig, InMemoryStore
from repro.data.cells import batch_for_cell
from repro.launch.mesh import make_host_mesh
from repro.train.loop import Trainer, TrainerConfig
from repro.train.state import restore_train_state


def main():
    store = InMemoryStore()
    ckpt = CheckpointConfig(interval_batches=4, policy="intermittent",
                            quant=None, async_write=False)

    # phase 1: train on a 4×2 mesh
    mesh8 = make_host_mesh(4, 2)
    bundle8 = get_cell("dlrm-rm2", "train_batch", mesh=mesh8, reduced=True)
    t1 = Trainer(bundle8, store, ckpt, TrainerConfig(total_steps=8))
    t1.init_or_restore()
    with mesh8:
        t1.state = jax.device_put(
            t1.state, jax.tree.map(lambda p: NamedSharding(mesh8, p),
                                   bundle8.state_pspecs(),
                                   is_leaf=lambda x: isinstance(x, P)))
        t1.run(8)
    print("phase 1: trained 8 steps on 8 devices; checkpointed at step 8")
    t1.manager.wait()
    t1.close()

    # phase 2: restore the same checkpoint on a 2×2 mesh (4 devices)
    mesh4 = make_host_mesh(2, 2)
    bundle4 = get_cell("dlrm-rm2", "train_batch", mesh=mesh4, reduced=True)
    t2 = Trainer(bundle4, store, ckpt, TrainerConfig(total_steps=12))
    start = t2.init_or_restore()
    with mesh4:
        shardings = jax.tree.map(lambda p: NamedSharding(mesh4, p),
                                 bundle4.state_pspecs(),
                                 is_leaf=lambda x: isinstance(x, P))
        t2.state = jax.device_put(t2.state, shardings)
        t2.run(4)
    print(f"phase 2: restored at step {start} onto 4 devices and trained to "
          f"{int(jax.device_get(t2.state.step))}")
    emb = t2.state.params["tables"]["emb_0"]
    print(f"   emb_0 now sharded as: {emb.sharding}")
    t2.close()
    print("elastic restore OK — same checkpoint, different mesh")


if __name__ == "__main__":
    main()
