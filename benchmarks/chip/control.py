#!/usr/bin/env python3
"""Readings of the controls and planted faults that the limits of
``correct`` are set against. The benchmark's own runs never run this.

    python3 benchmarks/chip/control.py --workload <name> --seeds 11 12 13

For a training cell, per seed, at the cell's own batch and tables:

* ``fp8`` — the reference computed with fp8 (e4m3) operands where the
  configuration states bf16, put in the program's place: its loss,
  gradient and update gaps against the float32 reference;
* ``half_batch`` — a planted fault: the reference's loss over half of the
  batch, the mean taken over the rest;
* ``bf16`` — a witness, not a control: the reference computed in the
  precision the configuration states, against itself in float32. Its
  gaps are what that precision alone makes, with no program involved;
* ``uniform_asym`` — the program's own quantizer with the adaptive range
  search switched off (its ``uniform_asym`` path), on 4096 rows of each
  table of the seed's state: its ``quant_excess`` over the reference
  adaptive quantizer; ``adaptive`` is the same reading of the sound path.

For a resume cell, per seed: ``bf16`` — the decoded rows of the program's
payloads rounded to bfloat16, the precision below the float32 the restore
states: their ``restore_gap``.

A state left unchanged reads ``update_gap`` 1 by its definition and needs
no run. One JSON line per seed and control on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def quant_readings(cell, cfg, traffic, seed, methods, rows=4096):
    """quant_excess of the program's quantizer under each of ``methods``
    against the reference adaptive quantizer, on rows of the seed's
    state; and the restore gap of bf16-rounded decoded rows."""
    import jax
    import numpy as np

    from repro.core import packing
    from repro.kernels.adaptive_quant import quant_pack

    import bench_harness as H
    from bench_ref import dequantize, stored_error

    q = traffic["checkpoint"]["quant"]
    bundle, base = H.build_bundle(cfg, seed)
    state = bundle.make_state()
    rng = np.random.default_rng([seed, 0xC0])
    out = {m: [0.0, 0.0] for m in methods}
    bf16_gap = 0.0
    for name, spec in bundle.tracked.items():
        idx = rng.integers(0, spec.rows, min(rows, spec.rows))
        x = np.asarray(jax.device_get(state.params["tables"][name][idx]))
        e_ref = float(np.sum(stored_error(x, q["bits"], q["method"],
                                          q["num_bins"], q["ratio"],
                                          np.float16)))
        for m in methods:
            pq = quant_pack(x, bits=q["bits"], method=m,
                            num_bins=q["num_bins"], ratio=q["ratio"])
            codes = packing.unpack_bits(
                packing.words_to_payload(np.asarray(pq.words), pq.count,
                                         q["bits"]),
                q["bits"], pq.count).reshape(x.shape)
            s16 = np.asarray(pq.scale, np.float16)
            deq = dequantize(codes, s16, np.asarray(pq.zero, np.float16))
            out[m][0] += float(np.sum(np.square(x - deq)))
            out[m][1] += e_ref
            if m == "adaptive":
                low = np.asarray(jax.numpy.asarray(deq, jax.numpy.bfloat16)
                                 .astype(jax.numpy.float32))
                tiny = np.maximum(s16.astype(np.float32), 1e-30)[:, None]
                bf16_gap = max(bf16_gap,
                               float(np.max(np.abs(low - deq) / tiny)))
    del state
    return {m: p / r - 1.0 for m, (p, r) in out.items()}, bf16_gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import bench_harness as H
    from bench_gen import BatchGen
    from bench_reftrain import load_model, reference_train, training_gaps
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = H.load_bench()
    cell = H.find(bench["workloads"], args.workload, "workload")
    cfg = H.load_json(H.ROOT, H.find(bench["configs"], cell["config"],
                                     "config")["file"])
    traffic = H.load_json(HERE, "traffic", f"{cell['traffic']}.json")
    model = load_model(cell["config"])
    for seed in args.seeds:
        if traffic["mode"] == "resume":
            _, gap = quant_readings(cell, cfg, traffic, seed, ["adaptive"])
            print(json.dumps({"seed": seed, "control": "bf16",
                              "restore_gap": gap}), flush=True)
            continue
        gen = BatchGen(cfg, traffic["ids"], seed)
        batches = [gen.make(i) for i in range(3)]
        ref = reference_train(model, cfg, seed, batches)
        for name, kw in (("fp8", dict(precision="fp8")),
                         ("half_batch", dict(half_batch=True)),
                         ("bf16", dict(precision="bf16"))):
            other = reference_train(model, cfg, seed, batches, **kw)
            gaps = training_gaps(other, ref)
            gaps.pop("excluded")
            print(json.dumps({"seed": seed, "control": name, **gaps}),
                  flush=True)
        excess, _ = quant_readings(cell, cfg, traffic, seed,
                                   ["uniform_asym", "adaptive"])
        for m, v in excess.items():
            print(json.dumps({"seed": seed, "control": m,
                              "quant_excess": v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
