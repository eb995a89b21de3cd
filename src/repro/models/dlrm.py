"""DLRM (Naumov et al., arXiv:1906.00091) — the paper's own model family.

dlrm-rm2 config: 13 dense, 26 sparse fields, dim 64, bottom MLP 13-512-256-64,
top MLP 512-512-256-1, dot-product interaction.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str = "dlrm-rm2"
    n_dense: int = 13
    vocab_sizes: Tuple[int, ...] = ()
    embed_dim: int = 64
    bot_mlp: Tuple[int, ...] = (512, 256, 64)
    top_mlp: Tuple[int, ...] = (512, 512, 256, 1)
    multi_hot: int = 1
    compute_dtype: object = jnp.bfloat16

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)

    @property
    def n_interact(self) -> int:
        f = self.n_sparse + 1
        return f * (f - 1) // 2

    @property
    def table_rows(self) -> int:
        return sum(self.vocab_sizes)


def init_params(key, cfg: DLRMConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    tables = init_tables(k1, cfg.vocab_sizes, cfg.embed_dim)
    dense = dict(
        bot=mlp_init(k2, (cfg.n_dense,) + cfg.bot_mlp),
        top=mlp_init(k3, (cfg.embed_dim + cfg.n_interact,) + cfg.top_mlp),
    )
    return dict(tables=tables, dense=dense)


def tracked_specs(cfg: DLRMConfig) -> Dict[str, TrackedSpec]:
    return table_specs(cfg.vocab_sizes, cfg.embed_dim)


def dot_interaction(feats: jax.Array) -> jax.Array:
    """feats (B, F, D) → lower-triangle pairwise dots (B, F(F-1)/2)."""
    z = jnp.einsum("bfd,bgd->bfg", feats, feats)
    f = feats.shape[1]
    iu, ju = np.triu_indices(f, k=1)
    return z[:, iu, ju]


def _logits(params, dense_x, sparse_ids, cfg: DLRMConfig, rules: ShardingRules,
            vectors=None):
    cd = cfg.compute_dtype
    with jax.named_scope("mlp"):
        bot = mlp_apply(params["dense"]["bot"], dense_x, final_act=True,
                        compute_dtype=cd)
    with jax.named_scope("lookup"):
        if vectors is not None:
            emb = vectors.sum(axis=2)                          # (B, F, D)
            emb = rules.shard(emb, "batch", None, None)
        else:
            emb = lookup_fields(params["tables"], sparse_ids, rules)  # (B, F, D)
    with jax.named_scope("interaction"):
        feats = jnp.concatenate([bot[:, None, :], emb.astype(cd)], axis=1)
        feats = rules.shard(feats, "batch", None, None)
        inter = dot_interaction(feats)
    with jax.named_scope("mlp"):
        top_in = jnp.concatenate([bot, inter], axis=-1)
        out = mlp_apply(params["dense"]["top"], top_in, compute_dtype=cd)
    return out[..., 0].astype(jnp.float32)


def train_loss(params, batch, cfg: DLRMConfig, rules: ShardingRules = NO_SHARDING):
    logits = _logits(params, batch["dense"], batch["sparse_ids"], cfg, rules)
    loss = bce_with_logits(logits, batch["label"])
    acc = jnp.mean((logits > 0) == (batch["label"] > 0.5))
    touched = touched_masks(cfg.vocab_sizes, batch["sparse_ids"])
    return loss, dict(accuracy=acc, touched=touched)


def make_sparse_train_step(cfg: DLRMConfig, rules: ShardingRules, dense_opt,
                           lr: float = 0.01, eps: float = 1e-8):
    """§Perf iteration R2: sparse embedding update.

    The generic train step differentiates w.r.t. the full tables — XLA
    materializes a dense table-shaped gradient and the row-wise AdaGrad
    update then streams EVERY row (read acc + param, write both) even though
    <1% of rows have non-zero gradient. Here gradients are taken w.r.t. the
    *gathered vectors* (B, F, H, D); per field the per-id gradients are
    dedup-aggregated (sort + segment-sum) and scattered back with exact
    row-wise-AdaGrad semantics — HBM traffic scales with touched rows, not
    table rows (≈500× less for the train_batch cell).

    Named scopes mark the step's phases in a profile: ``lookup``,
    ``backward`` (the loss and its gradient; the forward's own ``mlp`` and
    ``interaction`` scopes nest inside it), ``dense_update`` and
    ``sparse_update`` (the row-wise AdaGrad scatter).
    """
    import jax

    from ..optim.optimizers import apply_updates
    from ..train.state import TrainState

    F = cfg.n_sparse

    def gather_vectors(tables, ids):
        return jnp.stack([jnp.take(tables[f"emb_{i}"], ids[:, i, :], axis=0)
                          for i in range(F)], axis=1)        # (B,F,H,D)

    def loss_from(dense_params, vectors, batch):
        logits = _logits({"dense": dense_params, "tables": None},
                         batch["dense"], batch["sparse_ids"], cfg, rules,
                         vectors=vectors)
        loss = bce_with_logits(logits, batch["label"])
        acc = jnp.mean((logits > 0) == (batch["label"] > 0.5))
        return loss, acc

    def train_step(state: TrainState, batch):
        ids = batch["sparse_ids"]                             # (B,F,H)
        with jax.named_scope("lookup"):
            vectors = gather_vectors(state.params["tables"], ids)
        with jax.named_scope("backward"):
            (loss, acc_m), (g_dense, g_vec) = jax.value_and_grad(
                loss_from, argnums=(0, 1), has_aux=True)(
                    state.params["dense"], vectors, batch)

        with jax.named_scope("dense_update"):
            d_upd, d_state = dense_opt.update(
                g_dense, state.opt_state["dense"], state.params["dense"])
            new_dense = apply_updates(state.params["dense"], d_upd)

        tables = dict(state.params["tables"])
        accs = dict(state.opt_state["tables"])
        touched = dict(state.touched)
        with jax.named_scope("sparse_update"):
            for f in range(F):
                name = f"emb_{f}"
                V = tables[name].shape[0]
                idf = ids[:, f, :].reshape(-1)                    # (B·H,)
                g = g_vec[:, f, :, :].reshape(idf.shape[0], -1)   # (B·H, D)
                order = jnp.argsort(idf)
                ids_s = idf[order]
                g_s = jnp.take(g, order, axis=0)
                first = jnp.concatenate([jnp.ones((1,), bool),
                                         ids_s[1:] != ids_s[:-1]])
                seg = jnp.cumsum(first) - 1
                g_agg = jax.ops.segment_sum(g_s, seg, num_segments=idf.shape[0])
                g_rows = jnp.where(first[:, None], jnp.take(g_agg, seg, axis=0), 0.0)
                write_ids = jnp.where(first, ids_s, V)            # V ⇒ dropped
                acc_rows = jnp.take(accs[name], jnp.minimum(write_ids, V - 1))
                g2 = jnp.mean(jnp.square(g_rows), axis=-1)
                new_acc = acc_rows + g2
                upd = -lr * g_rows / (jnp.sqrt(new_acc)[:, None] + eps)
                tables[name] = tables[name].at[write_ids].add(
                    upd.astype(tables[name].dtype), mode="drop")
                accs[name] = accs[name].at[write_ids].set(new_acc, mode="drop")
                touched[name] = jnp.logical_or(
                    touched[name], jnp.zeros((V,), bool).at[idf].set(True, mode="drop"))

        new_state = TrainState(
            step=state.step + 1,
            params=dict(tables=tables, dense=new_dense),
            opt_state=dict(tables=accs, dense=d_state),
            touched=touched, rng=state.rng)
        return new_state, dict(loss=loss, accuracy=acc_m)

    return train_step


def serve(params, batch, cfg: DLRMConfig, rules: ShardingRules = NO_SHARDING):
    """Online/offline CTR scoring (serve_p99 / serve_bulk cells)."""
    logits = _logits(params, batch["dense"], batch["sparse_ids"], cfg, rules)
    return jax.nn.sigmoid(logits)


def serve_retrieval(params, batch, cfg: DLRMConfig,
                    rules: ShardingRules = NO_SHARDING):
    """retrieval_cand: one user context scored against C candidate items
    (candidates substitute sparse field 0). Batched over candidates — the
    user-side bottom MLP and non-candidate embeddings are computed once."""
    cd = cfg.compute_dtype
    dense_x = batch["dense"]            # (1, n_dense)
    sparse_ids = batch["sparse_ids"]    # (1, F, H) — field 0 ignored
    cand_ids = batch["candidate_ids"]   # (C,)
    C = cand_ids.shape[0]

    bot = mlp_apply(params["dense"]["bot"], dense_x, final_act=True, compute_dtype=cd)  # (1, D)
    emb = lookup_fields(params["tables"], sparse_ids, rules)  # (1, F, D)
    cand = jnp.take(params["tables"]["emb_0"], cand_ids, axis=0).astype(cd)  # (C, D)
    cand = rules.shard(cand, "candidates", None)

    fixed = jnp.concatenate([bot[:, None, :], emb[:, 1:, :].astype(cd)], axis=1)[0]  # (F, D)
    # pairwise dots among fixed feats (shared) + cand·fixed dots (per candidate)
    f = fixed.shape[0]
    iu, ju = np.triu_indices(f, k=1)
    fixed_dots = (fixed @ fixed.T)[iu, ju]  # (F(F-1)/2,)
    cand_dots = cand @ fixed.T              # (C, F)
    top_in = jnp.concatenate([
        jnp.broadcast_to(bot[0], (C, bot.shape[-1])),
        cand_dots,
        jnp.broadcast_to(fixed_dots, (C, fixed_dots.shape[0])),
    ], axis=-1)
    out = mlp_apply(params["dense"]["top"], top_in, compute_dtype=cd)
    return jax.nn.sigmoid(out[..., 0].astype(jnp.float32))
