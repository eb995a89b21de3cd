"""Plain reference of xDeepFM (Lian et al., arXiv:1803.05170) for the
``xdeepfm`` configuration: its initial weights from the seed, its forward
pass (linear term + compressed interaction network + deep MLP) and loss,
in straightforward ``jax.numpy``. It imports nothing of the program.

The weights are drawn as the configuration states them, from six splits
``ks`` of the seed key: each embedding table ``i`` (dim ``embed_dim``) from
``normal(split(ks[0])[i], (rows, dim)) / sqrt(dim)``, each linear table
(dim 1) from ``normal(split(ks[1])[i], (rows, 1))``, CIN layer ``i``'s
(H_i, H_{i-1}, F) weight from ``normal(fold_in(ks[2], i)) / sqrt(H_i)``,
the CIN output (sum H, 1) from ``ks[3]`` over ``sqrt(sum H)``, the deep
MLP from ``ks[4]`` with zero biases, and a zero scalar bias.

``cast`` is applied where the configuration states its compute precision:
at the looked-up vectors and every operand of a contraction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def table_names(cfg: dict) -> list:
    n = len(cfg["vocab_sizes"])
    return [f"emb_{i}" for i in range(n)] + [f"lin_{i}" for i in range(n)]


def table_field(name: str) -> int:
    return int(name.split("_")[1])


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_params(key, cfg: dict, rows_of):
    """Initial weights. ``rows_of(name, table)`` picks the rows of each
    freshly drawn table that the caller keeps."""
    ks = jax.random.split(key, 6)
    n_f, dim = len(cfg["vocab_sizes"]), cfg["embed_dim"]
    tables = {}
    for prefix, k, d in (("emb", ks[0], dim), ("lin", ks[1], 1)):
        keys = jax.random.split(k, n_f)
        for i, (ki, v) in enumerate(zip(keys, cfg["vocab_sizes"])):
            name = f"{prefix}_{i}"
            tables[name] = rows_of(name, _normal(ki, (v, d), 1.0 / np.sqrt(d)))
    cin, h_prev = [], n_f
    for i, h in enumerate(cfg["cin_layers"]):
        cin.append(_normal(jax.random.fold_in(ks[2], i), (h, h_prev, n_f),
                           1.0 / np.sqrt(h)))
        h_prev = h
    h_sum = sum(cfg["cin_layers"])
    dims = [n_f * dim] + cfg["mlp"] + [1]
    keys = jax.random.split(ks[4], len(dims) - 1)
    deep = [dict(w=_normal(k, (a, b), 1.0 / np.sqrt(a)),
                 b=jnp.zeros((b,), jnp.float32))
            for k, a, b in zip(keys, dims[:-1], dims[1:])]
    dense = dict(cin=cin,
                 cin_out=_normal(ks[3], (h_sum, 1), 1.0 / np.sqrt(h_sum)),
                 deep=deep, bias=jnp.zeros((), jnp.float32))
    return dict(tables=tables, dense=dense)


def _lookup(tables, prefix, ids):
    return jnp.stack([jnp.take(tables[f"{prefix}_{f}"], ids[:, f, :],
                               axis=0).sum(axis=1)
                      for f in range(ids.shape[1])], axis=1)


def logits(params, batch, cfg: dict, cast):
    ids = batch["sparse_ids"]
    d = params["dense"]
    emb = cast(_lookup(params["tables"], "emb", ids))          # (B, F, D)
    linear = jnp.sum(cast(_lookup(params["tables"], "lin", ids))[..., 0],
                     axis=-1)
    xk, pooled = emb, []
    for w in d["cin"]:
        z = jnp.einsum("bhd,bfd->bhfd", cast(xk), emb, precision=HIGHEST)
        xk = jnp.einsum("bhfd,ohf->bod", cast(z), cast(w), precision=HIGHEST)
        pooled.append(jnp.sum(xk, axis=-1))
    cin = jnp.dot(cast(jnp.concatenate(pooled, axis=-1)), cast(d["cin_out"]),
                  precision=HIGHEST)[:, 0]
    x = emb.reshape(emb.shape[0], -1)
    for i, layer in enumerate(d["deep"]):
        x = jnp.dot(cast(x), cast(layer["w"]), precision=HIGHEST) \
            + cast(layer["b"])
        if i < len(d["deep"]) - 1:
            x = jax.nn.relu(x)
    return linear + cin + x[:, 0] + d["bias"]


def loss(params, batch, cfg: dict, cast):
    x = logits(params, batch, cfg, cast)
    y = batch["label"]
    return jnp.mean(jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))))
