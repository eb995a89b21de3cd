"""xDeepFM (arXiv:1803.05170): linear + CIN (compressed interaction network)
+ deep MLP. Config: 39 sparse fields, dim 10, CIN 200-200-200, MLP 400-400.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import NO_SHARDING, ShardingRules
from ..train.state import TrackedSpec
from .embedding import (
    bce_with_logits,
    init_tables,
    lookup_fields,
    mlp_apply,
    mlp_init,
    table_specs,
    touched_masks,
)
from .layers import dense_init


@dataclasses.dataclass(frozen=True)
class XDeepFMConfig:
    name: str = "xdeepfm"
    vocab_sizes: Tuple[int, ...] = ()
    embed_dim: int = 10
    cin_layers: Tuple[int, ...] = (200, 200, 200)
    mlp: Tuple[int, ...] = (400, 400)
    multi_hot: int = 1
    compute_dtype: object = jnp.bfloat16

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)


def init_params(key, cfg: XDeepFMConfig):
    ks = jax.random.split(key, 6)
    F = cfg.n_sparse
    tables = init_tables(ks[0], cfg.vocab_sizes, cfg.embed_dim)
    tables.update(init_tables(ks[1], cfg.vocab_sizes, 1, prefix="lin"))
    cin_ws = []
    h_prev = F
    for i, h in enumerate(cfg.cin_layers):
        cin_ws.append(dense_init(jax.random.fold_in(ks[2], i), (h, h_prev, F)))
        h_prev = h
    dense = dict(
        cin=cin_ws,
        cin_out=dense_init(ks[3], (sum(cfg.cin_layers), 1)),
        deep=mlp_init(ks[4], (F * cfg.embed_dim,) + cfg.mlp + (1,)),
        bias=jnp.zeros(()),
    )
    return dict(tables=tables, dense=dense)


def tracked_specs(cfg: XDeepFMConfig) -> Dict[str, TrackedSpec]:
    specs = table_specs(cfg.vocab_sizes, cfg.embed_dim)
    specs.update(table_specs(cfg.vocab_sizes, 1, prefix="lin"))
    return specs


def cin(x0: jax.Array, weights, rules: ShardingRules,
        compute_dtype=jnp.bfloat16) -> jax.Array:
    """Compressed Interaction Network. x0 (B, F, D) → (B, sum(H_k))."""
    xk = x0
    pooled = []
    for w in weights:
        # z (B, H_{k-1}, F, D) = outer feature-map product, then compress
        z = jnp.einsum("bhd,bfd->bhfd", xk, x0)
        z = rules.shard(z, "batch", None, None, None)
        xk = jnp.einsum("bhfd,ohf->bod", z, w.astype(compute_dtype))
        pooled.append(jnp.sum(xk, axis=-1))  # (B, H_k)
    return jnp.concatenate(pooled, axis=-1)


def _logits(params, sparse_ids, cfg: XDeepFMConfig, rules: ShardingRules):
    cd = cfg.compute_dtype
    with jax.named_scope("lookup"):
        emb = lookup_fields(params["tables"], sparse_ids, rules).astype(cd)  # (B,F,D)
        lin = lookup_fields(params["tables"], sparse_ids, rules, prefix="lin")  # (B,F,1)
    linear_term = jnp.sum(lin[..., 0].astype(jnp.float32), axis=-1)
    with jax.named_scope("cin"):
        cin_feats = cin(emb, params["dense"]["cin"], rules, cd)
        cin_term = (cin_feats @ params["dense"]["cin_out"].astype(cd))[..., 0]
    B = emb.shape[0]
    with jax.named_scope("mlp"):
        deep_term = mlp_apply(params["dense"]["deep"], emb.reshape(B, -1),
                              compute_dtype=cd)[..., 0]
    return (linear_term + cin_term.astype(jnp.float32)
            + deep_term.astype(jnp.float32) + params["dense"]["bias"])


def train_loss(params, batch, cfg: XDeepFMConfig, rules: ShardingRules = NO_SHARDING):
    logits = _logits(params, batch["sparse_ids"], cfg, rules)
    loss = bce_with_logits(logits, batch["label"])
    acc = jnp.mean((logits > 0) == (batch["label"] > 0.5))
    touched = touched_masks(cfg.vocab_sizes, batch["sparse_ids"])
    touched.update(touched_masks(cfg.vocab_sizes, batch["sparse_ids"], prefix="lin"))
    return loss, dict(accuracy=acc, touched=touched)


def serve(params, batch, cfg: XDeepFMConfig, rules: ShardingRules = NO_SHARDING):
    return jax.nn.sigmoid(_logits(params, batch["sparse_ids"], cfg, rules))


def serve_retrieval(params, batch, cfg: XDeepFMConfig,
                    rules: ShardingRules = NO_SHARDING):
    """retrieval_cand: tile the single user row across candidates on field 0.
    Chunked over candidates to bound the CIN intermediate."""
    sparse_ids = batch["sparse_ids"]          # (1, F, H)
    cand_ids = batch["candidate_ids"]         # (C,)
    C = cand_ids.shape[0]
    chunk = 8192

    def score_chunk(ids_chunk):
        ids = jnp.broadcast_to(sparse_ids, (ids_chunk.shape[0],) + sparse_ids.shape[1:])
        ids = ids.at[:, 0, :].set(ids_chunk[:, None])
        return _logits(params, ids, cfg, rules)

    n_chunks = max(C // chunk, 1)
    cand_chunks = cand_ids[: n_chunks * chunk].reshape(n_chunks, -1)
    scores = jax.lax.map(score_chunk, cand_chunks).reshape(-1)
    return jax.nn.sigmoid(scores)
