"""Push-time smoke slice of the nightly training-stack suites.

test_models_smoke.py and test_distribution.py are ``slow``-marked (the
full 40-cell × multi-mesh sweep is multi-minute) and only run on the
scheduled job — which means a push that breaks ``get_cell`` or the mesh
plumbing sails through fast CI. This file keeps a deliberately tiny,
reduced-shape cross-section of both suites in the ``-m "not slow"`` set:
one training cell per model family plus one 4-device equivalence check.

Full shapes and the remaining cells stay nightly-only.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_cell
from repro.data.cells import batch_for_cell

# one train cell per family: recommendation (the paper's target), sequence
# recommendation, and the LM stack — all at reduced shapes (seconds each)
SMOKE_CELLS = [("dlrm-rm2", "train_batch"),
               ("bert4rec", "train_batch"),
               ("qwen2-0.5b", "train_4k")]


@pytest.mark.parametrize("arch,shape", SMOKE_CELLS,
                         ids=[f"{a}-{s}" for a, s in SMOKE_CELLS])
def test_reduced_cell_trains_one_step(arch, shape):
    bundle = get_cell(arch, shape, reduced=True)
    batch = batch_for_cell(bundle, 0)
    state = bundle.make_state()
    state2, metrics = jax.jit(bundle.step_fn)(state, batch)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    assert int(jax.device_get(state2.step)) == 1
    for name, spec in bundle.tracked.items():
        assert state2.touched[name].shape == (spec.units,)


def test_reduced_sharded_train_matches_single_device():
    """2×2 emulated mesh == single device for one reduced dlrm step.

    Subprocess so --xla_force_host_platform_device_count never leaks into
    the main pytest process (the cell smokes above must see 1 device)."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_cell
        from repro.data.cells import batch_for_cell
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 2)
        b1 = get_cell("dlrm-rm2", "train_batch", reduced=True)
        bm = get_cell("dlrm-rm2", "train_batch", mesh=mesh, reduced=True)
        batch = batch_for_cell(b1, 0)
        s1, m1 = jax.jit(b1.step_fn)(b1.make_state(), batch)
        with mesh:
            state = bm.make_state()
            sh = jax.tree.map(
                lambda p: NamedSharding(mesh, p if p is not None else P()),
                bm.state_pspecs(),
                is_leaf=lambda x: x is None or isinstance(x, P))
            state = jax.device_put(state, sh)
            s2, m2 = jax.jit(bm.step_fn)(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        print("OK")
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
