"""CPU tests of a cell whose configuration names a device mesh
(``program.mesh``): whole runs of a four-chip cell of a tiny DLRM-RM2 on
four virtual CPU devices, and the refusal of a mesh whose size is not the
cell's chips. The runs go in a subprocess, so that the forced device count
never reaches the pytest process. Nothing here edits ``BENCHMARK.json``:
the four-chip cell is added to a copy of it in memory."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench_harness as H  # noqa: E402
import test_chip_bench_faults as F  # noqa: E402

MESH = {"shape": [2, 2], "axes": ["data", "model"]}
CONFIG = "dlrm-rm2-cap2m"


def mesh_cell(mix: str, chips: int = 4):
    """A copy of the benchmark with a cell of ``chips`` chips for the tiny
    DLRM-RM2 under ``mix``, and the overrides that name the 2x2 mesh."""
    bench = H.load_bench()
    name = f"dlrm-rm2-2x2.{mix}"
    bench["workloads"].append({"name": name, "config": CONFIG, "traffic": mix,
                               "chips": chips, "why": "a 2x2 mesh on the CPU"})
    cfg = H.load_json(H.ROOT,
                      H.find(bench["configs"], CONFIG, "config")["file"])
    ov = F.overrides(f"dlrm-rm2.{mix}")
    ov["config"]["program"] = dict(cfg["program"], mesh=MESH)
    return bench, name, ov


CHILD = """
import json, sys
import bench_harness as H
import test_chip_bench_faults as F
import test_chip_bench_mesh as M

H.WORK = sys.argv[2]            # off the work directory other tests use
bench, name, ov = M.mesh_cell(sys.argv[1])
tables = []
real = H.Sampler.take

def take(self, state, idx):
    # the placement of every table at each boundary the check samples
    tables.append({n: [len(a.sharding.device_set),
                       list(a.sharding.shard_shape(a.shape)), list(a.shape)]
                   for n, a in state.params["tables"].items()})
    return real(self, state, idx)

H.Sampler.take = take
res = H.run_cell(name, F.SEED, 1.5, False, require_tpu=False, overrides=ov,
                 bench=bench)
print(json.dumps(dict(result=res, tables=tables)))
"""


def run_child(mix: str, work) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), HERE]))
    r = subprocess.run([sys.executable, "-c", CHILD, mix, str(work)],
                       cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=150)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-8000:]}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_mesh_cell_trains_sharded_on_four_devices(tmp_path):
    out = run_child("incr4-zipf", tmp_path)
    res = out["result"]
    assert res["correct"], res["checks"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["attempted"] >= 1 and res["failed"] == 0
    dev = res["device"]
    assert dev["count"] == 4 and len(dev["memory_peak_bytes_by_chip"]) == 4
    assert dev["memory_peak_bytes"] == max(dev["memory_peak_bytes_by_chip"])
    # every table's rows are split over the four devices at every boundary
    assert out["tables"]
    for placed in out["tables"]:
        for n_dev, shard, shape in placed.values():
            assert n_dev == 4 and shard == [shape[0] // 4] + shape[1:]


def test_mesh_cell_resumes_on_four_devices(tmp_path):
    # The restore places the restored arrays without the template's
    # shardings (train/state.restore_train_state), so the resumed tables
    # land on one device; placing them on the mesh is program work left to
    # the configuration that needs it. Only ``correct`` is held here.
    res = run_child("resume-chain", tmp_path)["result"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4


def test_mesh_size_other_than_the_cells_chips_is_refused(monkeypatch):
    def never(*a, **kw):
        raise AssertionError("state built before the mesh was checked")

    monkeypatch.setattr(H, "build_bundle", never)
    monkeypatch.setattr(H, "Run", never)
    bench, name, ov = mesh_cell("incr4-zipf", chips=1)
    with pytest.raises(SystemExit, match=r"\[2, 2\].*1 chip"):
        H.run_cell(name, F.SEED, 1.5, False, require_tpu=False, overrides=ov,
                   bench=bench)
