"""Decoupled in-memory snapshots (Check-N-Run §3.2).

Training stalls only while the sharded model state is copied device→host
(the paper's <7 s GPU→DRAM copy on 128 GPUs). Everything downstream —
policy decision, quantization, packing, storage — runs in background threads
on the snapshot, while training proceeds on device.

On a real multi-host pod each host calls ``take_snapshot`` on its own
addressable shards; here (single process) that is all shards.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Union

import jax
import numpy as np

from . import trace


@dataclasses.dataclass
class Snapshot:
    step: int
    tables: Dict[str, np.ndarray]                 # name -> (rows, dim) f32
    row_state: Dict[str, Dict[str, np.ndarray]]   # name -> aux -> (rows,) arrays
    touched: Dict[str, np.ndarray]                # name -> (rows,) bool
    dense: Dict[str, np.ndarray]                  # flat path -> ndarray
    extra: Dict[str, Any]                         # JSON-serializable

    def total_param_bytes(self) -> int:
        n = sum(t.nbytes for t in self.tables.values())
        n += sum(a.nbytes for d in self.row_state.values() for a in d.values())
        n += sum(a.nbytes for a in self.dense.values())
        return n

    def copied_bytes(self) -> int:
        """Bytes the device→host copy moved: the state and the touched
        masks."""
        return self.total_param_bytes() + sum(
            t.nbytes for t in self.touched.values())


def _to_host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def take_snapshot(
    step: Union[int, jax.Array],
    tables: Dict[str, jax.Array],
    row_state: Dict[str, Dict[str, jax.Array]],
    touched: Dict[str, jax.Array],
    dense: Dict[str, jax.Array],
    extra: Dict[str, Any],
) -> Snapshot:
    """Atomic device→host copy; the only part that stalls training.

    Two spans split it: ``cnr.snapshot.drain`` waits for the work already
    dispatched on the arrays (and reads ``step``, which may be a device
    scalar), ``cnr.snapshot.copy`` is the copy itself, with its ``bytes``.
    The copy would wait for that work anyway; the drain only names it."""
    with trace.span("cnr.snapshot.drain") as sp:
        jax.block_until_ready((tables, row_state, touched, dense))
        step = int(jax.device_get(step))
        sp.set(request=step, step=step)
    with trace.span("cnr.snapshot.copy", request=step, step=step) as sp:
        snap = Snapshot(
            step=step,
            tables={k: _to_host(v) for k, v in tables.items()},
            row_state={k: {a: _to_host(v) for a, v in d.items()}
                       for k, d in row_state.items()},
            touched={k: _to_host(v) for k, v in touched.items()},
            dense={k: _to_host(v) for k, v in dense.items()},
            extra=dict(extra),
        )
        sp.set(bytes=snap.copied_bytes())
    return snap
