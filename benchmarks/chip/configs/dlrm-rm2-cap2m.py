"""Plain reference of DLRM (Naumov et al., arXiv:1906.00091) for the
``dlrm-rm2-cap2m`` configuration: its initial weights from the seed, its
forward pass and loss, in straightforward ``jax.numpy``. It imports
nothing of the program.

The weights are drawn as the configuration states them: each table ``i``
from ``normal(k_i, (rows, dim)) / sqrt(dim)`` with ``k_i`` the i-th split
of the first of three splits of the seed key; each MLP layer's weight from
``normal(k, (d_in, d_out)) / sqrt(d_in)`` and a zero bias. The loss is the
mean binary cross-entropy of the logits.

``cast`` is applied where the configuration states its compute precision:
at every operand of a matrix product and at the looked-up vectors. The
reference passes float32; the control passes a lower precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def table_names(cfg: dict) -> list:
    return [f"emb_{i}" for i in range(len(cfg["vocab_sizes"]))]


def table_field(name: str) -> int:
    return int(name.split("_")[1])


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _mlp_init(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [dict(w=_normal(k, (a, b), 1.0 / np.sqrt(a)),
                 b=jnp.zeros((b,), jnp.float32))
            for k, a, b in zip(keys, dims[:-1], dims[1:])]


def init_params(key, cfg: dict, rows_of):
    """Initial weights. ``rows_of(name, table)`` picks the rows of each
    freshly drawn table that the caller keeps."""
    k1, k2, k3 = jax.random.split(key, 3)
    dim = cfg["embed_dim"]
    keys = jax.random.split(k1, len(cfg["vocab_sizes"]))
    tables = {}
    for i, (k, v) in enumerate(zip(keys, cfg["vocab_sizes"])):
        name = f"emb_{i}"
        tables[name] = rows_of(name, _normal(k, (v, dim), 1.0 / np.sqrt(dim)))
    n_feat = len(cfg["vocab_sizes"]) + 1
    n_inter = n_feat * (n_feat - 1) // 2
    dense = dict(bot=_mlp_init(k2, [cfg["n_dense"]] + cfg["bot_mlp"]),
                 top=_mlp_init(k3, [dim + n_inter] + cfg["top_mlp"]))
    return dict(tables=tables, dense=dense)


def _mlp(layers, x, cast, final_act):
    n = len(layers)
    for i, layer in enumerate(layers):
        x = jnp.dot(cast(x), cast(layer["w"]), precision=HIGHEST) \
            + cast(layer["b"])
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def logits(params, batch, cfg: dict, cast):
    """``batch["sparse_ids"]`` (B, F, H) index the rows of ``params``'s
    tables; each field's vectors are summed over H."""
    ids = batch["sparse_ids"]
    bot = _mlp(params["dense"]["bot"], batch["dense"], cast, final_act=True)
    emb = jnp.stack([jnp.take(params["tables"][f"emb_{f}"], ids[:, f, :],
                              axis=0).sum(axis=1)
                     for f in range(ids.shape[1])], axis=1)
    feats = jnp.concatenate([bot[:, None, :], cast(emb)], axis=1)
    z = jnp.einsum("bfd,bgd->bfg", cast(feats), cast(feats),
                   precision=HIGHEST)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    top_in = jnp.concatenate([bot, z[:, iu, ju]], axis=-1)
    return _mlp(params["dense"]["top"], top_in, cast, final_act=False)[:, 0]


def loss(params, batch, cfg: dict, cast):
    x = logits(params, batch, cfg, cast)
    y = batch["label"]
    return jnp.mean(jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x))))
