"""Serving launcher: batched request loop with live checkpoint refresh.

Demonstrates the paper's *online training* consumer side: an inference
process serves batched requests from a model it periodically refreshes from
the newest valid Check-N-Run checkpoint (full or increment chain) — the
checkpoint cadence bounds serving staleness.

Each refresh here is a full ``restore()`` because the whole TrainState is
rebuilt. Replicas that serve *embeddings only* should use the delta
subscriber instead (``repro.serve`` / ``ckpt subscribe --follow``,
docs/serving.md): it pays touched-row bytes per refresh, not model bytes.

  PYTHONPATH=src python -m repro.launch.serve --arch dlrm-rm2 \
      --ckpt-dir /tmp/ckpts --requests 200 --batch 64 --refresh-every 50
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm2")
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--refresh-every", type=int, default=50)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from .compile_cache import enable_compile_cache

    enable_compile_cache()

    from ..configs import get_cell
    from ..core import CheckNRunManager, CheckpointConfig, LocalFSStore
    from ..core import manifest as mf
    from ..data.cells import batch_for_cell
    from ..train.state import restore_train_state

    store = LocalFSStore(args.ckpt_dir)
    if mf.latest_step(store) is None:
        print(f"no checkpoints in {args.ckpt_dir}; run repro.launch.train first")
        return 1

    # serve_p99 is the online-inference cell of every recsys arch
    bundle = get_cell(args.arch, "serve_p99", reduced=True)
    mgr = CheckNRunManager(store, CheckpointConfig())
    serve_fn = jax.jit(bundle.step_fn)

    def load_latest():
        restored = mgr.restore()
        state = restore_train_state(bundle.make_state(), restored, bundle.tracked)
        return state.params, restored.step

    params, step = load_latest()
    print(f"serving {args.arch} from checkpoint step {step}")
    lat = []
    served = 0
    for i in range(args.requests // args.batch + 1):
        if served and served % args.refresh_every == 0:
            new_step = mf.latest_step(store)
            if new_step != step:
                params, step = load_latest()
                print(f"  refreshed to checkpoint step {step} "
                      f"(staleness reset after {served} requests)")
        batch = batch_for_cell(bundle, 50_000 + i)
        t0 = time.monotonic()
        out = serve_fn(params, batch)
        jax.block_until_ready(out)
        lat.append(time.monotonic() - t0)
        served += int(np.shape(jax.tree_util.tree_leaves(out)[0])[0] or 1)
        if served >= args.requests:
            break
    lat_ms = sorted(1e3 * t for t in lat)
    print(f"served {served} requests in {len(lat)} batches; "
          f"p50 {lat_ms[len(lat_ms)//2]:.2f} ms  "
          f"p99 {lat_ms[int(len(lat_ms)*0.99)]:.2f} ms per batch")
    return 0


if __name__ == "__main__":
    sys.exit(main())
