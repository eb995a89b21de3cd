"""Distribution-equivalence tests on emulated multi-device meshes.

Each test runs in a subprocess with --xla_force_host_platform_device_count
so the forced device count never leaks into the main pytest process (smoke
tests must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

from tests.conftest import cell_shard

# multi-minute training-stack tests: excluded from the fast CI set
# (`-m "not slow"`), exercised by the scheduled full job — sharded across
# a CI matrix via CNR_CELL_SHARD="i/n" (see conftest.cell_shard)
pytestmark = pytest.mark.slow

_N_MESH_TESTS = 3


def _shard_guard(idx: int) -> None:
    """Skip unless this mesh test's index lands in the active CI shard."""
    if idx not in cell_shard(list(range(_N_MESH_TESTS))):
        pytest.skip(f"assigned to another CNR_CELL_SHARD shard "
                    f"({os.environ.get('CNR_CELL_SHARD')})")


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def test_ep_moe_equals_dense_dispatch():
    _shard_guard(0)
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np, dataclasses, jax.numpy as jnp
        from repro.dist.sharding import lm_rules
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as m_tf
        from repro.models.layers import MoEConfig
        mesh = make_host_mesh(2, 4)
        rules = lm_rules(mesh)
        cfg_ep = m_tf.TransformerConfig(
            name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
            head_dim=16, d_ff=128, vocab=512, act="silu", gated=True,
            moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, gated=True,
                          capacity_factor=8.0, dispatch="ep"))
        cfg_dn = dataclasses.replace(
            cfg_ep, moe=dataclasses.replace(cfg_ep.moe, dispatch="dense"))
        params = m_tf.init_params(jax.random.key(0), cfg_ep)
        toks = np.random.default_rng(0).integers(0, 512, (8, 32)).astype(np.int32)
        batch = dict(tokens=jnp.asarray(toks), labels=jnp.asarray((toks + 1) % 512))
        with mesh:
            l_ep, a_ep = jax.jit(lambda p, b: m_tf.train_loss(p, b, cfg_ep, rules))(params, batch)
            l_dn, a_dn = jax.jit(lambda p, b: m_tf.train_loss(p, b, cfg_dn, rules))(params, batch)
        assert abs(float(l_ep) - float(l_dn)) < 2e-2, (float(l_ep), float(l_dn))
        assert (np.asarray(a_ep["touched"]["moe_w_up"])
                == np.asarray(a_dn["touched"]["moe_w_up"])).all()
        print("OK")
    """)


def test_sharded_dimenet_equals_plain():
    _shard_guard(1)
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np, jax.numpy as jnp
        from repro.dist.sharding import gnn_rules
        from repro.launch.mesh import make_host_mesh
        from repro.models import dimenet as m_dn
        mesh = make_host_mesh(2, 4)
        rules = gnn_rules(mesh)
        cfg = m_dn.DimeNetConfig(name="t", n_blocks=2, d_hidden=16,
                                 n_bilinear=2, n_spherical=3, n_radial=2,
                                 d_feat=24, n_out=5)
        params = m_dn.init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        N, E = 64, 128
        src = rng.integers(0, N, E).astype(np.int32)
        dst = rng.integers(0, N, E).astype(np.int32)
        ji = np.arange(E, dtype=np.int32)
        kj = (ji // 16) * 16 + rng.integers(0, 16, E).astype(np.int32)
        batch = {k: jnp.asarray(v) for k, v in dict(
            features=rng.normal(size=(N, 24)).astype(np.float32),
            edge_src=src, edge_dst=dst, tri_kj=kj, tri_ji=ji).items()}
        plain = m_dn.forward_flat(params, batch, cfg)
        with mesh:
            shard = jax.jit(lambda p, b: m_dn.forward_flat_sharded(p, b, cfg, rules))(params, batch)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(shard),
                                   rtol=3e-2, atol=3e-2)
        print("OK")
    """)


def test_sharded_train_matches_single_device():
    """One dlrm train step on a 2×2 mesh produces the same loss/params as
    the single-device step (sharding must not change semantics)."""
    _shard_guard(2)
    _run("""
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
            sh = jax.tree.map(lambda p: NamedSharding(mesh, p if p is not None else P()),
                              bm.state_pspecs(),
                              is_leaf=lambda x: x is None or isinstance(x, P))
            state = jax.device_put(state, sh)
            s2, m2 = jax.jit(bm.step_fn)(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        a = np.asarray(s1.params["tables"]["emb_0"])
        c = np.asarray(jax.device_get(s2.params["tables"]["emb_0"]))
        np.testing.assert_allclose(a, c, rtol=1e-3, atol=1e-5)
        print("OK")
    """)
