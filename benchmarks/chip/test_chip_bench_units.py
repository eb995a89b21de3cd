"""CPU tests of the benchmark's yardstick: trace reduction, kernel byte
and FLOP counts, the names in BENCHMARK.json, and that every cell's files
are found by name. Nothing here loads the TPU library."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench_trace as T  # noqa: E402
import bench_yardstick as Y  # noqa: E402
from bench_ref import (chunk_hash32, quantize, unpack_rows,  # noqa: E402
                       words_checksum)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ trace
def synthetic_trace():
    # device ops on [0, 10]: busy 1-3, 2-4 (overlapping), 6-7; modules
    ops = [[(1.0, 3.0, "fusion.1"), (2.0, 4.0, "fusion.2"),
            (6.0, 7.0, "custom-call.3")]]
    mods = [[(1.0, 4.0, "jit_train_step(7)"),
             (6.0, 7.0, "jit_quant_pack_pallas(9)")]]
    spans = [(0.0, 10.0, "bench.window"), (4.0, 6.0, "bench.checkpoint"),
             (4.5, 5.0, "bench.put")]
    return T.Trace(ops=ops, modules=mods, spans=spans)


def test_trace_busy_idle_and_gaps():
    s = T.summarize(synthetic_trace())
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(4.0)
    assert s.idle_share() == pytest.approx(0.6)
    assert s.module_s == {"train_step": 3.0, "quant_pack_pallas": 1.0}
    # gaps 0-1, 4-6, 7-10: the innermost open span names each part
    assert s.gaps_by_span == pytest.approx(
        {"host.other": 4.0, "bench.checkpoint": 1.5, "bench.put": 0.5})


@pytest.mark.parametrize("name,key", [
    ("jit_train_step(12)", "train_step"),
    ("jit(chunk_hash_pallas)", "chunk_hash_pallas"),
    ("jit_quant_pack_pallas", "quant_pack_pallas"),
    ("plain_module", "plain_module"),
])
def test_module_key(name, key):
    assert T.module_key(name) == key


def test_trace_window_clips_and_requires_window():
    tr = synthetic_trace()
    tr.spans = [(2.0, 6.5, "bench.window")]
    s = T.summarize(tr)
    assert s.busy_s == pytest.approx(2.0 + 0.5)
    with pytest.raises(ValueError):
        T.summarize(T.Trace(ops=[[]], modules=[[]], spans=[]))


def test_trace_leaves_out_planes_without_ops():
    tr = synthetic_trace()
    tr.ops.append([])             # a device plane with no ops (Megascale)
    tr.modules.append([])
    s = T.summarize(tr)
    assert s.n_chips == 1
    assert s.busy_s == pytest.approx(4.0)
    assert s.idle_share() == pytest.approx(0.6)


def test_trace_four_chips_mean_busy_and_summed_modules():
    # chips busy 2, 4, 6 and 2 s of [0, 10]; every chip idle over 6-7, 9-10
    busy = [(1.0, 3.0), (1.0, 5.0), (0.0, 6.0), (7.0, 9.0)]
    tr = T.Trace(ops=[[(s, e, "fusion")] for s, e in busy] + [[]],
                 modules=[[(s, e, "jit_train_step(3)")] for s, e in busy]
                 + [[]],
                 spans=[(0.0, 10.0, "bench.window"),
                        (6.0, 7.0, "bench.checkpoint")])
    s = T.summarize(tr)
    assert s.n_chips == 4
    assert s.busy_s == pytest.approx(3.5)
    assert s.idle_share() == pytest.approx(0.65)
    assert s.module_s == pytest.approx({"train_step": 14.0})
    assert s.gaps_by_span == pytest.approx(
        {"bench.checkpoint": 1.0, "host.other": 1.0})


@pytest.mark.parametrize("n_chips", [1, 4])
def test_train_step_ms_and_save_mfu_read_per_chip(n_chips):
    import bench_harness as H

    # every chip runs 100 steps of 20 ms; two saves of 0.5 s each
    rec = H.RunRecord(cell={"chips": n_chips}, cfg={},
                      traffic={"mode": "train"},
                      peaks=Y.peaks_for("TPU v5 lite"), steps=100,
                      saves=[dict(rows_by_dim={64: 1000}, nbytes=40_000,
                                  durable_s=0.5)] * 2)
    rec.trace = T.Summary(window_s=10.0, busy_s=2.0,
                          module_s={"train_step": 2.0 * n_chips},
                          gaps_by_span={}, n_chips=n_chips)
    assert H.load_reader("train_step_ms")(rec) == pytest.approx(20.0)
    least = 2 * (1000 * 64 * 4 + 40_000) / (819e9 * n_chips)
    assert H.load_reader("save_mfu")(rec) == pytest.approx(100.0 * least)


def test_memory_peaks_read_every_device():
    import bench_harness as H

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert H.memory_peaks([Dev({"peak_bytes_in_use": 5}),
                           Dev({"peak_bytes_in_use": 9}), Dev(None),
                           Dev({})]) == [5, 9, 0, 0]


# ------------------------------------------------- byte and FLOP counts
@pytest.mark.parametrize("rows,dim,bits,want", [
    # f32 rows in + packed words out + f32 scale and zero per row
    (65536, 64, 4, 65536 * 64 * 4 + 65536 * 64 * 4 // 32 * 4 + 65536 * 8),
    (1000, 10, 4, 1000 * 40 + 1250 * 4 + 1000 * 8),
    (1001, 1, 4, 1001 * 4 + 126 * 4 + 1001 * 8),   # 1001 codes -> 126 words
])
def test_quant_pack_bytes(rows, dim, bits, want):
    assert Y.quant_pack_bytes(rows, dim, bits) == want


def test_chunk_hash_and_save_bytes():
    assert Y.chunk_hash_bytes(10) == 12
    assert Y.chunk_hash_bytes(32) == 32
    assert Y.save_device_bytes({64: 100, 1: 10}, 7) == 100 * 256 + 40 + 7


def test_model_flops_by_hand():
    dlrm = dict(arch="dlrm-rm2", n_dense=13, bot_mlp=[512, 256, 64],
                top_mlp=[512, 512, 256, 1], embed_dim=64,
                vocab_sizes=[1] * 26, batch=2)
    bot = 2 * (13 * 512 + 512 * 256 + 256 * 64)
    inter = 2 * 27 * 27 * 64
    top = 2 * ((64 + 351) * 512 + 512 * 512 + 512 * 256 + 256 * 1)
    assert Y.dlrm_forward_flops(dlrm) == bot + inter + top
    assert Y.train_step_flops(dlrm) == 3 * (bot + inter + top) * 2
    xd = dict(arch="xdeepfm", embed_dim=10, cin_layers=[200, 200, 200],
              mlp=[400, 400], vocab_sizes=[1] * 39, batch=1)
    cin = sum(2 * hp * 39 * 10 + 2 * h * hp * 39 * 10
              for hp, h in ((39, 200), (200, 200), (200, 200)))
    deep = 2 * (390 * 400 + 400 * 400 + 400 * 1)
    assert Y.xdeepfm_forward_flops(xd) == cin + deep


def test_peaks_table():
    assert Y.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        Y.peaks_for("TPU v9 imaginary")


# --------------------------------------------------------- references
def test_unpack_rows_matches_bit_layout():
    rng = np.random.default_rng(0)
    for bits, dim in ((4, 64), (4, 10), (4, 1), (3, 10)):
        codes = rng.integers(0, 1 << bits, (37, dim), dtype=np.uint8)
        bitstream = np.zeros(codes.size * bits, np.uint8)
        for p, c in enumerate(codes.reshape(-1)):
            for b in range(bits):
                bitstream[p * bits + b] = (c >> b) & 1
        packed = np.packbits(bitstream, bitorder="little").tobytes()
        rows = np.array([0, 5, 36, 17])
        assert (unpack_rows(packed, bits, dim, rows) == codes[rows]).all()


def test_chunk_hash_and_checksum_by_hand():
    # one word: t = mix(w + 0), acc = t, h = finalize(t + 1 * P5)
    w = 0x01020304
    t = (w * 1) & 0xFFFFFFFF
    t ^= t >> 15
    t = (t * 0x9E3779B1) & 0xFFFFFFFF
    t ^= t >> 13
    t = (t * 0xC2B2AE3D) & 0xFFFFFFFF
    h = (t + 0x165667B1) & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x9E3779B1) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE3D) & 0xFFFFFFFF
    h ^= h >> 16
    assert chunk_hash32(w.to_bytes(4, "little")) == h
    assert words_checksum(b"\x01\0\0\0\x02\0\0\0") == (3, 1 * 1 + 2 * 2)


def test_reference_quantizer_adaptive_beats_uniform():
    x = np.random.default_rng(1).standard_normal((512, 64)).astype(np.float32)

    def err(method):
        c, s, z = quantize(x, 4, method, 45, 0.2)
        return float(np.sum(np.square(x - (c * s[:, None] + z[:, None]))))

    assert err("adaptive") < 0.95 * err("uniform_asym")


# ------------------------------------------------ BENCHMARK.json itself
def test_benchmark_names_units_and_keys():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]]
             + [w["traffic"] for w in b["workloads"]]
             + [k for c in b["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    import bench_harness as H

    b = bench()
    for w in b["workloads"]:
        e2e, per = H.cell_metrics(b, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert per and all(m["moves"] in names for m in per)


def test_each_cell_finds_its_files_by_name():
    import bench_harness as H

    b = bench()
    for w in b["workloads"]:
        cfg = H.find(b["configs"], w["config"], "config")
        assert cfg["file"].startswith(b["paths"][0] + "/")
        with open(os.path.join(ROOT, cfg["file"])) as f:
            assert json.load(f)["name"] == w["config"]
        assert os.path.exists(os.path.join(HERE, "configs",
                                           f"{w['config']}.py"))
        with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
            assert json.load(f)["mode"] in ("train", "resume")
    for m in b["per_layer"]:
        assert callable(H.load_reader(m["name"]))


def test_a_new_cell_and_metric_load_from_added_files_alone(tmp_path):
    """Copy the benchmark, add a traffic file, a metric reader and their
    entries, and see the copy's harness find them without an edit."""
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, dst, ignore=shutil.ignore_patterns(
        ".work", "__pycache__"))
    b = bench()
    with open(dst / "traffic" / "incr4-zipf.json") as f:
        tr = json.load(f)
    tr["ids"]["law"] = "uniform"
    with open(dst / "traffic" / "incr4-uniform.json", "w") as f:
        json.dump(tr, f)
    (dst / "metrics" / "saves_in_window.py").write_text(
        "def read(rec):\n    return float(len(rec.saves))\n")
    b["workloads"].append({"name": "dlrm-rm2.incr4-uniform",
                           "config": "dlrm-rm2-cap2m",
                           "traffic": "incr4-uniform", "chips": 1,
                           "why": "uniform ids"})
    for m in b["end_to_end"]:
        if "dlrm-rm2.incr4-zipf" in m.get("workloads", []):
            m["workloads"].append("dlrm-rm2.incr4-uniform")
    b["per_layer"].append({"name": "saves_in_window", "unit": "saves",
                           "better": "higher", "source": "program_counter",
                           "layer": "Write pipeline", "moves": "durable_s",
                           "workloads": ["dlrm-rm2.incr4-uniform"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    spec = importlib.util.spec_from_file_location(
        "bench_harness_copy", dst / "bench_harness.py")
    h = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = h
    try:
        spec.loader.exec_module(h)
    finally:
        del sys.modules[spec.name]
    b2 = h.load_bench()
    e2e, per = h.cell_metrics(b2, "dlrm-rm2.incr4-uniform")
    assert [m["name"] for m in per] == ["saves_in_window"]
    assert "durable_s" in {m["name"] for m in e2e}
    traffic = h.load_json(h.HERE, "traffic", "incr4-uniform.json")
    assert traffic["ids"]["law"] == "uniform"
    rec = h.RunRecord(cell={}, cfg={}, traffic=traffic, peaks={},
                      saves=[{}, {}])
    assert h.load_reader("saves_in_window")(rec) == 2.0


def test_readers_stay_silent_without_their_data():
    import bench_harness as H

    rec = H.RunRecord(cell={}, cfg={}, traffic={"mode": "train"}, peaks={})
    for m in bench()["per_layer"]:
        assert H.load_reader(m["name"])(rec) is None, m["name"]


def test_log_uniform_ids_stay_in_range_and_skew():
    from bench_gen import BatchGen

    g = BatchGen({"vocab_sizes": [1000, 7, 2_000_384], "batch": 4096,
                  "n_dense": 3}, {"law": "log_uniform", "multi_hot": 2},
                 seed=2 ** 33 + 5)
    b = g(0)
    ids = b["sparse_ids"]
    assert ids.shape == (4096, 3, 2) and ids.dtype == np.int32
    assert ids.min() >= 0 and (ids.max(axis=(0, 2)) < [1000, 7, 2_000_384]).all()
    # log-uniform: half the draws land under sqrt(V)
    assert 0.4 < np.mean(ids[:, 2] < math.sqrt(2_000_384)) < 0.6
    assert (g.make(0)["sparse_ids"] == ids).all()
    assert not (g.make(1)["sparse_ids"] == ids).all()
