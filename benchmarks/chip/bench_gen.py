"""The one traffic generator: recommendation-model batches from a seed.

A traffic mix is a data file (``traffic/<mix>.json``); this module reads
its ``ids`` block and makes every batch a pure function of ``(seed,
batch index)``, so a run, its reference and a later check all see the same
rows. Nothing here imports the program.

Sparse ids follow a log-uniform rank law per field (rank ``r`` drawn as
``floor(exp(u ln V)) - 1`` with ``u`` uniform), the heavy-tailed access
skew of production embedding tables, as the paper's Figures 3 and 4
describe: a few hot rows take most lookups and each interval touches a
power-law share of every table.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

_STREAM = 0xC4E7


def _rng(seed: int, idx: int) -> np.random.Generator:
    return np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(seed), int(idx), _STREAM])))


def log_uniform_ids(rng: np.random.Generator, vocab_sizes, shape) -> np.ndarray:
    """ids (shape + (F,)) with field ``f`` drawn log-uniformly over
    ``[0, vocab_sizes[f])``."""
    log_v = np.log(np.maximum(np.asarray(vocab_sizes, np.float64), 2.0))
    u = rng.random(tuple(shape) + (len(log_v),), np.float32)
    u *= log_v.astype(np.float32)
    ids = np.exp(u, out=u).astype(np.int32) - 1
    return np.minimum(ids, np.asarray(vocab_sizes, np.int32) - 1)


def uniform_ids(rng: np.random.Generator, vocab_sizes, shape) -> np.ndarray:
    v = np.asarray(vocab_sizes, np.int64)
    u = rng.random(tuple(shape) + (len(v),), np.float32)
    return np.minimum((u * v).astype(np.int64), v - 1).astype(np.int32)


ID_LAWS = {"log_uniform": log_uniform_ids, "uniform": uniform_ids}


class BatchGen:
    """Batches of one configuration under one traffic mix.

    ``cfg`` is the configuration file's dict (``vocab_sizes``, ``batch``,
    ``n_dense``), ``ids`` the traffic file's ``ids`` block (``law``,
    ``multi_hot``). The most recent batches are kept, so the harness can
    sample rows that an interval touched without drawing them again."""

    def __init__(self, cfg: dict, ids: dict, seed: int, keep: int = 64):
        self.vocab_sizes = list(cfg["vocab_sizes"])
        self.batch = int(cfg["batch"])
        self.n_dense = int(cfg.get("n_dense", 0))
        self.multi_hot = int(ids.get("multi_hot", 1))
        self.law = ID_LAWS[ids["law"]]
        self.seed = int(seed)
        self.keep = keep
        self._recent: Dict[int, Dict[str, np.ndarray]] = {}

    def __call__(self, idx: int) -> Dict[str, np.ndarray]:
        out = self.make(idx)
        self._recent[idx] = out
        for old in [k for k in self._recent if k <= idx - self.keep]:
            del self._recent[old]
        return out

    def recent(self, idx: int) -> Optional[Dict[str, np.ndarray]]:
        return self._recent.get(idx)

    def make(self, idx: int) -> Dict[str, np.ndarray]:
        rng = _rng(self.seed, idx)
        b, h = self.batch, self.multi_hot
        ids = self.law(rng, self.vocab_sizes, (b, h))        # (B, H, F)
        out = {"sparse_ids": np.ascontiguousarray(ids.transpose(0, 2, 1))}
        logit = np.zeros(b, np.float32)
        if self.n_dense:
            dense = rng.random((b, self.n_dense), np.float32)
            dense = dense * 2.0 - 1.0
            w = np.linspace(-0.3, 0.3, self.n_dense, dtype=np.float32)
            logit += dense @ w
            out["dense"] = dense
        # a learnable teacher: the parity of the first field's id nudges
        # the label, so the loss is not flat
        logit += np.where(ids[:, 0, 0] % 2 == 0, 0.5, -0.5)
        p = 1.0 / (1.0 + np.exp(-logit))
        out["label"] = (rng.random(b, np.float32) < p).astype(np.float32)
        return out

    def touched(self, first: int, last: int) -> Dict[int, np.ndarray]:
        """field -> sorted unique ids of batches ``first .. last - 1``,
        drawn again from the seed."""
        cols: Dict[int, list] = {f: [] for f in range(len(self.vocab_sizes))}
        for i in range(first, last):
            ids = self.make(i)["sparse_ids"]
            for f in cols:
                cols[f].append(ids[:, f, :].reshape(-1))
        return {f: np.unique(np.concatenate(c)) for f, c in cols.items()}
