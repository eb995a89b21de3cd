"""CPU tests that drive whole benchmark runs at a tiny size, past the
harness's look for a chip: a sound run is ``correct`` and prints the
result line's keys in order; a run with the timed path broken underneath
is not — a step that returns its state unchanged, half of the batch left
out, a payload altered where it is produced, a restore that returns other
values; and the controls read above the limits."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench_harness as H  # noqa: E402

TINY = {
    "dlrm-rm2-cap2m": dict(vocab_sizes=[3000, 40, 700, 2000], embed_dim=16,
                           bot_mlp=[32, 16], top_mlp=[32, 16, 1], batch=256),
    "xdeepfm": dict(vocab_sizes=[3000, 40, 700, 33, 90], embed_dim=10,
                    cin_layers=[8, 8], mlp=[16, 16], batch=128),
}
SEED = 2 ** 31 + 1234


@pytest.fixture(autouse=True)
def restore_jax_cache_config():
    """A run turns on the persistent compile cache; put the worker's
    settings back for the tests that follow in this process."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def overrides(cell: str) -> dict:
    b = H.load_bench()
    w = H.find(b["workloads"], cell, "workload")
    tr = H.load_json(H.HERE, "traffic", f"{w['traffic']}.json")
    ck = dict(tr["checkpoint"], chunk_rows=512,
              interval_batches=4 if w["config"] != "xdeepfm" else 6)
    return {"config": TINY[w["config"]],
            "traffic": dict(checkpoint=ck,
                            check=dict(tr["check"], rows_per_table=16))}


def run(cell: str, seconds: float = 1.5) -> dict:
    return H.run_cell(cell, SEED, seconds, False, require_tpu=False,
                      overrides=overrides(cell))


def failing(res: dict) -> list:
    return [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", ["dlrm-rm2.incr4-zipf", "xdeepfm.full4",
                                  "dlrm-rm2.resume-chain"])
def test_sound_run_is_correct_and_prints_the_keys(cell):
    res = run(cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    import jax
    from repro.train import loop

    real = loop._jitted_step

    def unchanged(step_fn):
        step = real(step_fn)

        def go(state, batch):
            new, metrics = step(jax.tree.map(lambda x: x.copy(), state), batch)
            return dataclasses_replace(state, step=new.step,
                                       touched=new.touched), metrics
        return go

    monkeypatch.setattr(loop, "_jitted_step", unchanged)
    res = run("dlrm-rm2.incr4-zipf")
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def dataclasses_replace(state, **kw):
    from repro.train.state import TrainState

    d = dict(step=state.step, params=state.params, opt_state=state.opt_state,
             touched=state.touched, rng=state.rng)
    d.update(kw)
    return TrainState(**d)


def test_half_of_the_batch_left_out_fails(monkeypatch):
    from repro.train import loop

    real = loop._jitted_step

    def halved(step_fn):
        step = real(step_fn)
        return lambda state, batch: step(
            state, {k: v[: len(v) // 2] for k, v in batch.items()})

    monkeypatch.setattr(loop, "_jitted_step", halved)
    res = run("dlrm-rm2.incr4-zipf")
    assert not res["correct"]
    assert {"loss_gap", "grad_gap"} & set(failing(res))


def test_payload_altered_where_produced_fails(monkeypatch):
    from repro.core import packing

    real = packing.words_to_payload

    def altered(words, count, bits):
        b = bytearray(real(words, count, bits))
        b[len(b) // 2] ^= 0x10
        return bytes(b)

    monkeypatch.setattr(packing, "words_to_payload", altered)
    res = run("dlrm-rm2.incr4-zipf")
    assert not res["correct"]
    assert "hash_mismatch" in failing(res)


def test_restore_returning_other_values_fails(monkeypatch):
    from repro.core import checkpoint

    real = checkpoint.dequantize

    def off(q):
        return np.asarray(real(q)) * np.float32(1.001)

    monkeypatch.setattr(checkpoint, "dequantize", off)
    res = run("dlrm-rm2.resume-chain")
    assert not res["correct"]
    assert failing(res) == ["restore_gap"]


def test_controls_read_above_the_limits():
    """The fp8 reference in place of the program, and the program's
    quantizer with its adaptive search off, at the tiny size."""
    from bench_gen import BatchGen
    from bench_reftrain import load_model, reference_train, training_gaps

    b = H.load_bench()
    w = H.find(b["workloads"], "dlrm-rm2.incr4-zipf", "workload")
    cfg = H.load_json(H.ROOT, H.find(b["configs"], w["config"], "c")["file"])
    cfg.update(TINY[w["config"]])
    tr = H.load_json(H.HERE, "traffic", f"{w['traffic']}.json")
    model = load_model(w["config"])
    batches = [BatchGen(cfg, tr["ids"], SEED).make(i) for i in range(3)]
    ref = reference_train(model, cfg, SEED, batches)
    gaps = training_gaps(reference_train(model, cfg, SEED, batches,
                                         precision="fp8"), ref)
    assert any(gaps[k] > cfg["limits"][k] for k in cfg["limits"]), gaps

    import control
    excess, bf16_gap = control.quant_readings(w, cfg, tr, SEED,
                                              ["uniform_asym", "adaptive"],
                                              rows=256)
    assert excess["uniform_asym"] > tr["limits"]["quant_excess"]
    assert excess["adaptive"] <= tr["limits"]["quant_excess"]
    rtr = H.load_json(H.HERE, "traffic", "resume-chain.json")
    assert bf16_gap > rtr["limits"]["restore_gap"]
