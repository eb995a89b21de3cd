"""Train state + the adapter between model pytrees and Check-N-Run snapshots.

Conventions (repro-wide):
  * ``params = {"tables": {name: (rows, dim)}, "dense": {...nested...}}`` —
    ``tables`` are row-sharded embedding tables trained with row-wise AdaGrad;
    everything else lives under ``dense``.
  * Tracked state is declared by ``TrackedSpec``s: embedding tables trivially
    (1 unit = 1 row), and optionally *dense* parameter blocks with coarser
    touched units — e.g. MoE expert stacks, where a unit is one (layer,
    expert) pair and ``expansion`` maps it to the 2-D row view the
    checkpointer quantizes (a beyond-paper extension of the paper's
    row-granular idea).
  * ``state.touched[name]`` is a bool vector of ``units`` per tracked spec,
    updated inside the jitted train step (tracker.py) and reset after each
    snapshot.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.snapshot import Snapshot
from ..core.tracker import init_touched


@dataclasses.dataclass(frozen=True)
class TrackedSpec:
    """Declares one incrementally-checkpointed parameter block."""

    path: Tuple[str, ...]        # into params, e.g. ("tables", "emb_3")
    units: int                   # tracked units (rows / (layer,expert) pairs)
    rows: int                    # rows of the 2-D checkpoint view
    dim: int                     # columns of the 2-D checkpoint view
    rowwise_aux: bool = True     # include per-row optimizer aux ((rows,) acc)

    @property
    def expansion(self) -> int:
        assert self.rows % self.units == 0
        return self.rows // self.units


def tree_get(tree, path: Tuple[str, ...]):
    for k in path:
        tree = tree[k]
    return tree


def tree_set(tree, path: Tuple[str, ...], value):
    if len(path) == 1:
        return {**tree, path[0]: value}
    return {**tree, path[0]: tree_set(tree[path[0]], path[1:], value)}


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    touched: Dict[str, jax.Array]
    rng: jax.Array

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.touched, self.rng), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_train_state(params, optimizer, specs: Dict[str, TrackedSpec],
                     rng: jax.Array) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
        touched={name: init_touched(s.units) for name, s in specs.items()},
        rng=rng,
    )


# ------------------------------------------------------- snapshot adapters


def _flatten_dense(tree, prefix="") -> Dict[str, np.ndarray]:
    out = {}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves:
        key = prefix + jax.tree_util.keystr(path)
        out[key] = leaf
    return out


def state_to_snapshot(state: TrainState, specs: Dict[str, TrackedSpec],
                      extra: Dict[str, Any]) -> Snapshot:
    """Build the Check-N-Run snapshot view of a train state (host copy
    happens in take_snapshot — here we only slice the pytree)."""
    from ..core.snapshot import take_snapshot

    tables: Dict[str, jax.Array] = {}
    row_state: Dict[str, Dict[str, jax.Array]] = {}
    touched: Dict[str, jax.Array] = {}
    tracked_paths = set()
    for name, spec in specs.items():
        arr = tree_get(state.params, spec.path)
        tables[name] = arr.reshape(spec.rows, spec.dim)
        tracked_paths.add(spec.path)
        aux: Dict[str, jax.Array] = {}
        opt_leaf = _find_opt_leaf(state.opt_state, spec.path)
        if opt_leaf is not None:
            if opt_leaf.shape == (spec.rows,):
                aux["opt_acc"] = opt_leaf
            else:
                aux["opt_acc2d"] = opt_leaf.reshape(spec.rows, -1) if opt_leaf.ndim else opt_leaf
        row_state[name] = aux
        mask = state.touched[name]
        if spec.expansion > 1:
            mask = jnp.repeat(mask, spec.expansion)
        touched[name] = mask

    dense_params = {}
    for key, leaf in _flatten_dense(state.params["dense"], prefix="params").items():
        dense_params[key] = leaf
    # exclude tracked dense paths from the dense dump
    for name, spec in specs.items():
        if spec.path[0] == "dense":
            key = "params" + "".join(f"['{k}']" for k in spec.path[1:])
            dense_params.pop(key, None)
    dense_opt = _flatten_dense(_prune_tracked_opt(state.opt_state, specs), prefix="opt")
    dense_all = {**dense_params, **dense_opt,
                 "step": state.step, "rng": jax.random.key_data(state.rng)}

    return take_snapshot(
        step=state.step,
        tables=tables, row_state=row_state, touched=touched,
        dense=dense_all, extra=extra)


def _find_opt_leaf(opt_state, path: Tuple[str, ...]):
    """Locate the optimizer accumulator matching a tracked param path.

    split_optimizer state mirrors the params structure under the same keys
    (tables → rowwise acc (rows,), dense adagrad → acc with param shape)."""
    try:
        return tree_get(opt_state, path)
    except (KeyError, TypeError):
        return None


def _prune_tracked_opt(opt_state, specs: Dict[str, TrackedSpec]):
    pruned = opt_state
    for spec in specs.values():
        try:
            sub = tree_get(pruned, spec.path[:-1])
            if spec.path[-1] in sub:
                new_sub = {k: v for k, v in sub.items() if k != spec.path[-1]}
                pruned = tree_set(pruned, spec.path[:-1], new_sub) if len(spec.path) > 1 \
                    else {k: v for k, v in pruned.items() if k != spec.path[0]}
        except (KeyError, TypeError):
            continue
    return pruned


def restore_train_state(template: TrainState, restored,
                        specs: Dict[str, TrackedSpec],
                        shardings: Optional[Any] = None) -> TrainState:
    """Rebuild a TrainState from a RestoredState, matching the template's
    structure. Works across mesh sizes (elastic restore): host arrays are
    device_put with the template/sharding layout."""
    state = template
    params = state.params
    opt = state.opt_state
    for name, spec in specs.items():
        orig = tree_get(params, spec.path)
        new_val = jnp.asarray(restored.tables[name].reshape(orig.shape), dtype=orig.dtype)
        params = tree_set(params, spec.path, new_val)
        aux = restored.row_state.get(name, {})
        opt_leaf = _find_opt_leaf(opt, spec.path)
        if opt_leaf is not None and "opt_acc" in aux:
            opt = tree_set(opt, spec.path, jnp.asarray(aux["opt_acc"], dtype=opt_leaf.dtype))
        elif opt_leaf is not None and "opt_acc2d" in aux:
            opt = tree_set(opt, spec.path,
                           jnp.asarray(aux["opt_acc2d"].reshape(opt_leaf.shape), dtype=opt_leaf.dtype))

    dense_flat = dict(restored.dense)
    params = _restore_dense(params, {k[len("params"):]: v for k, v in dense_flat.items()
                                     if k.startswith("params")})
    opt = _restore_dense(opt, {k[len("opt"):]: v for k, v in dense_flat.items()
                               if k.startswith("opt")}, root=("",))
    step = jnp.asarray(dense_flat["step"], jnp.int32) if "step" in dense_flat \
        else jnp.asarray(restored.step, jnp.int32)
    rng = (jax.random.wrap_key_data(jnp.asarray(dense_flat["rng"]))
           if "rng" in dense_flat else template.rng)
    touched = {name: jnp.zeros_like(template.touched[name]) for name in template.touched}
    new_state = TrainState(step=step, params=params, opt_state=opt,
                           touched=touched, rng=rng)
    if shardings is not None:
        new_state = jax.device_put(new_state, shardings)
    return new_state


def splice_shard_state(state: TrainState, restored,
                       specs: Dict[str, TrackedSpec]) -> TrainState:
    """Overwrite ONLY one recovered shard's rows of a live TrainState.

    ``restored`` is a ``CheckNRunManager.restore_part`` result: shard-sized
    table/aux arrays plus ``extra["shard"]["row_range"]`` naming each
    table's ``[lo, hi)``. Every row outside the ranges — including all of
    the dense params/opt and the step/rng — keeps its LIVE value: this is
    the CPR staleness model (only the failed shard rolls back to the
    checkpoint) and the exact-mode shard splice (where the caller first
    rebuilt the survivors from the boundary snapshot, so "live" already
    means "at the committed step").

    The spliced rows' touched bits are re-fenced to False: they now hold
    the last committed values, so a since-last-commit touched claim for
    them is stale (the manager-side mask twin is
    ``CheckNRunManager.refence_shard``). For coarse-tracked specs
    (``expansion > 1``) only units FULLY COVERED by the range are
    cleared: a resharded recovery's ranges need not be unit-aligned, and
    a partial unit still carries live rows whose touched claim must
    survive (re-storing an already-committed row is merely redundant;
    losing a legitimate claim would drop data from the next increment).
    """
    shard = (restored.extra or {}).get("shard") or {}
    ranges = shard.get("row_range") or {}
    params = state.params
    opt = state.opt_state
    touched = dict(state.touched)
    for name, spec in specs.items():
        if name not in restored.tables or name not in ranges:
            continue
        lo, hi = ranges[name]
        orig = tree_get(params, spec.path)
        flat = orig.reshape(spec.rows, spec.dim)
        flat = flat.at[lo:hi].set(
            jnp.asarray(restored.tables[name], dtype=orig.dtype))
        params = tree_set(params, spec.path, flat.reshape(orig.shape))
        aux = restored.row_state.get(name, {})
        opt_leaf = _find_opt_leaf(opt, spec.path)
        if opt_leaf is not None and "opt_acc" in aux:
            opt = tree_set(opt, spec.path, opt_leaf.at[lo:hi].set(
                jnp.asarray(aux["opt_acc"], dtype=opt_leaf.dtype)))
        elif opt_leaf is not None and "opt_acc2d" in aux:
            flat_o = opt_leaf.reshape(spec.rows, -1)
            flat_o = flat_o.at[lo:hi].set(
                jnp.asarray(aux["opt_acc2d"], dtype=opt_leaf.dtype))
            opt = tree_set(opt, spec.path, flat_o.reshape(opt_leaf.shape))
        ulo = -(-lo // spec.expansion)  # ceil — first fully-covered unit
        uhi = hi // spec.expansion      # floor — one past the last
        if ulo < uhi:
            touched[name] = touched[name].at[ulo:uhi].set(False)
    return TrainState(step=state.step, params=params, opt_state=opt,
                      touched=touched, rng=state.rng)


def _restore_dense(tree, flat: Dict[str, np.ndarray], root=("dense",)):
    """Write flattened host arrays back into the pytree by keystr match."""
    if root == ("dense",):
        sub = tree["dense"]
        paths = jax.tree_util.tree_flatten_with_path(sub)[0]
        new_leaves = {}
        for path, leaf in paths:
            key = jax.tree_util.keystr(path)
            if key in flat:
                new_leaves[key] = jnp.asarray(np.asarray(flat[key]).reshape(leaf.shape),
                                              dtype=leaf.dtype)
        rebuilt = jax.tree_util.tree_map_with_path(
            lambda p, l: new_leaves.get(jax.tree_util.keystr(p), l), sub)
        return {**tree, "dense": rebuilt}
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    new_leaves = {}
    for path, leaf in paths:
        key = jax.tree_util.keystr(path)
        if key in flat:
            new_leaves[key] = jnp.asarray(np.asarray(flat[key]).reshape(leaf.shape),
                                          dtype=leaf.dtype)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: new_leaves.get(jax.tree_util.keystr(p), l), tree)
