"""Error-feedback int8 gradient compression tests."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.dist.collectives import (
    compress_leaf,
    dequantize_int8,
    ef_allreduce_shardmap,
    init_residuals,
    quantize_int8,
)


def test_int8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    codes, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(codes, scale)) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-7


def test_error_feedback_unbiased_over_time():
    """Accumulated transmitted signal converges to the accumulated gradient
    (the residual stays bounded) — the EF guarantee."""
    rng = np.random.default_rng(1)
    g_total = np.zeros((32,), np.float32)
    sent_total = np.zeros((32,), np.float32)
    residual = jnp.zeros((32,), jnp.float32)
    for t in range(200):
        g = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
        codes, scale, residual = compress_leaf(g, residual)
        sent_total += np.asarray(dequantize_int8(codes, scale))
        g_total += np.asarray(g)
    # residual bounded => totals close
    drift = np.abs(g_total - sent_total).max()
    assert drift <= float(np.abs(np.asarray(residual)).max()) + 1e-4
    assert np.abs(np.asarray(residual)).max() < 1.0


def test_ef_allreduce_multidevice_subprocess():
    """Runs the shard_map EF all-reduce on 4 emulated devices (subprocess so
    the forced device count does not leak into this test process)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import ef_allreduce_shardmap, init_residuals
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
        res = jnp.zeros((4, 128), jnp.float32)
        def cell(g_l, r_l):
            m, r = ef_allreduce_shardmap({"g": g_l}, {"g": r_l}, "data")
            return m["g"], r["g"]
        with mesh:
            mean, new_res = jax.jit(shard_map(
                cell, mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=(P(None), P("data")), check_rep=False))(g, res)
        exact = np.asarray(g).reshape(4, 1, 128).mean(axis=0)
        got = np.asarray(mean)[:1]
        rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
        assert rel < 0.02, rel     # int8 compression error ~1/127
        print("OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return env
