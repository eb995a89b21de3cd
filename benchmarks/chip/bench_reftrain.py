"""The reference's first training steps, and the comparison of the
program's first steps with them.

The reference draws its own initial weights from the seed through the
configuration's plain reference (``configs/<config>.py``), keeps only the
rows that the steps' batches touch (an untouched row of a sparse model
neither moves nor has a gradient), and takes the configuration's
optimizer steps in float32: row-wise AdaGrad on each table (one
accumulator a row, the mean of the row's squared gradient) and AdaGrad on
every dense leaf. Nothing here imports the program.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

CASTS = {
    "f32": None,
    "bf16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
}


def load_model(config_name: str):
    """The plain reference module beside ``configs/<config_name>.json``."""
    path = os.path.join(HERE, "configs", f"{config_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_model_{config_name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cast_for(precision: str):
    dt = CASTS[precision]
    if dt is None:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


def leaf_norms(tree, prefix: str) -> Dict[str, jax.Array]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[prefix + jax.tree_util.keystr(path)] = jnp.sqrt(
            jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


def reference_train(model, cfg: dict, seed: int, batches: List[dict],
                    precision: str = "f32", half_batch: bool = False,
                    ) -> dict:
    """Take ``len(batches)`` optimizer steps from the seed's weights.
    Returns the loss of each step, each leaf's gradient norm at the first
    step and each leaf's change after the last (leaves named
    ``tables/<name>`` and ``dense<path>``). ``half_batch`` plants a fault:
    each loss is the mean over the first half of the batch only."""
    lr, eps = cfg["optimizer"]["lr"], cfg["optimizer"]["eps"]
    cast = cast_for(precision)
    names = model.table_names(cfg)
    n_fields = len(cfg["vocab_sizes"])
    ids = [b["sparse_ids"] for b in batches]
    span = sum(x[:, 0, :].size for x in ids)
    uniq = [np.unique(np.concatenate([x[:, f, :].ravel() for x in ids]))
            for f in range(n_fields)]
    pad = {n: np.pad(uniq[model.table_field(n)],
                     (0, span - len(uniq[model.table_field(n)])))
           for n in names}
    local = []
    for b in batches:
        lb = {k: v for k, v in b.items() if k != "sparse_ids"}
        sid = np.empty_like(b["sparse_ids"])
        for f in range(n_fields):
            sid[:, f, :] = np.searchsorted(uniq[f], b["sparse_ids"][:, f, :])
        lb["sparse_ids"] = sid
        if half_batch:
            lb = {k: v[: len(v) // 2] for k, v in lb.items()}
        local.append(lb)

    init = jax.jit(lambda key, idx: model.init_params(
        key, cfg, lambda name, t: t[idx[name]]))
    params0 = init(jax.random.key(seed), pad)

    def step(params, acc, batch):
        loss, g = jax.value_and_grad(model.loss)(params, batch, cfg, cast)
        tables, acc_t = {}, {}
        for n, p in params["tables"].items():
            a = acc["tables"][n] + jnp.mean(jnp.square(g["tables"][n]), axis=1)
            tables[n] = p - lr * g["tables"][n] / (jnp.sqrt(a)[:, None] + eps)
            acc_t[n] = a
        acc_d = jax.tree.map(lambda a, gi: a + jnp.square(gi), acc["dense"],
                             g["dense"])
        dense = jax.tree.map(lambda p, gi, a: p - lr * gi / (jnp.sqrt(a) + eps),
                             params["dense"], g["dense"], acc_d)
        norms = {**{f"tables/{n}": jnp.sqrt(jnp.sum(jnp.square(gt)))
                    for n, gt in g["tables"].items()},
                 **leaf_norms(g["dense"], "dense")}
        return (dict(tables=tables, dense=dense),
                dict(tables=acc_t, dense=acc_d), loss, norms)

    step = jax.jit(step)
    acc = dict(tables={n: jnp.zeros((t.shape[0],), jnp.float32)
                       for n, t in params0["tables"].items()},
               dense=jax.tree.map(jnp.zeros_like, params0["dense"]))
    params, losses, grads = params0, [], None
    for lb in local:
        params, acc, loss, norms = step(params, acc, lb)
        losses.append(float(loss))
        if grads is None:
            grads = {k: float(v) for k, v in norms.items()}
    change = jax.jit(lambda p, p0: {
        **{f"tables/{n}": jnp.sqrt(jnp.sum(jnp.square(p["tables"][n]
                                                      - p0["tables"][n])))
           for n in p["tables"]},
        **leaf_norms(jax.tree.map(jnp.subtract, p["dense"], p0["dense"]),
                     "dense")})(params, params0)
    return dict(loss=losses, grad=grads,
                change={k: float(v) for k, v in change.items()})


def training_gaps(prog: dict, ref: dict, rel_floor: float = 1e-3) -> dict:
    """The three numbers that compare the program's first steps with the
    reference's, each by its worst case:

    * ``loss_gap`` — over the steps, |loss - ref| / |ref|;
    * ``grad_gap`` — over the leaves, the gap between the program's and
      the reference's first-step gradient norms, over the larger of the
      reference leaf's norm and the median leaf's;
    * ``update_gap`` — the same for each leaf's change over the steps,
      leaving out leaves whose reference gradient is under ``rel_floor``
      of the median leaf's (such a leaf moves by round-off alone).
    """
    lp, lr_ = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    rel = np.abs(lp - lr_) / np.abs(lr_)
    loss_gap = float(np.max(rel))
    names = sorted(ref["grad"])
    gr = np.array([ref["grad"][n] for n in names])
    gp = np.array([prog["grad"][n] for n in names])
    med_g = float(np.median(gr))
    grad_gap = float(np.max(np.abs(gp - gr) / np.maximum(gr, med_g)))
    keep = gr >= rel_floor * med_g
    cr = np.array([ref["change"][n] for n in names])[keep]
    cp = np.array([prog["change"][n] for n in names])[keep]
    med_c = float(np.median(cr))
    update_gap = float(np.max(np.abs(cp - cr) / np.maximum(cr, med_c)))
    return dict(loss_gap=loss_gap, grad_gap=grad_gap, update_gap=update_gap,
                excluded=[n for n, k in zip(names, keep) if not k])
