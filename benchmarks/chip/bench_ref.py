"""Plain references for the checkpoint path: the textbook quantizer, the
payload decoder, the chunk hash oracle, a reader of the store's files and
the chain replay. Straightforward numpy over the on-disk format; nothing
here imports the program or takes anything it made but the stored bytes
that are being checked.

On-disk format read here (a ``LocalFSStore`` root):

* ``manifests/ckpt_<step:012d>.json`` — one committed save: ``kind``
  (``full`` | ``incremental``), ``prev_step``, ``base_step``, ``quant``,
  ``nbytes_total``, ``wall_time_s``, ``tables`` and ``dense``;
* a table's chunk is one blob of sections ``[indices][scale][zero][codes]
  [aux:<name>...]`` at ``[offset, nbytes]``: ``indices`` uint32 global rows
  (incremental chunks; full chunks carry ``row_range``), ``scale`` and
  ``zero`` one value a row in the table's ``meta_dtype``, ``codes`` the
  little-endian bit stream (code ``p`` at stream bit ``bits * p``), and
  each row-state column raw;
* a dense blob is the leaf's raw bytes.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

# ------------------------------------------------------------ chunk hash
_P1, _P2, _P3, _P5 = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x165667B1
_MASK = 0xFFFFFFFF


def chunk_hash32(payload: bytes) -> int:
    """The 32-bit chunk hash over a section: its bytes as little-endian
    uint32 words, zero-padded to a whole word; each word mixed with its
    index, the terms summed mod 2**32, the count folded in, avalanched."""
    pad = (-len(payload)) % 4
    w = np.frombuffer(payload + b"\0" * pad, "<u4").astype(np.uint32)
    i = (np.arange(w.size, dtype=np.uint64) & _MASK).astype(np.uint32)
    t = w + i * np.uint32(_P2)
    t ^= t >> np.uint32(15)
    t *= np.uint32(_P1)
    t ^= t >> np.uint32(13)
    t *= np.uint32(_P3)
    acc = int(np.sum(t, dtype=np.uint64) & _MASK)
    h = (acc + w.size * _P5) & _MASK
    h ^= h >> 16
    h = (h * _P1) & _MASK
    h ^= h >> 13
    h = (h * _P3) & _MASK
    h ^= h >> 16
    return h


# ------------------------------------------------------------- quantizer
def _affine_error(x, lo, hi, levels):
    rng = hi - lo
    scale = np.where(rng > 0, rng / levels, np.float32(1.0)).astype(np.float32)
    q = np.round((np.clip(x, lo[:, None], hi[:, None]) - lo[:, None])
                 / scale[:, None])
    q = np.clip(q, 0, levels)
    deq = q * scale[:, None] + lo[:, None]
    return np.sum(np.square(x - deq), axis=-1, dtype=np.float32)


def quantize(x: np.ndarray, bits: int, method: str, num_bins=None,
             ratio=None) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-wise asymmetric quantization in float32, the paper's §4.2:
    ``uniform_asym`` takes each row's [min, max]; ``adaptive`` starts there
    and greedily shrinks the lower or the upper end by (max - min) /
    num_bins, whichever errs less, for ``floor(ratio * num_bins)`` steps,
    keeping the range of least squared error seen. Returns (codes uint8,
    scale f32, zero f32)."""
    x = np.asarray(x, np.float32)
    levels = np.float32((1 << bits) - 1)
    lo0, hi0 = x.min(axis=-1), x.max(axis=-1)
    best_lo, best_hi = lo0, hi0
    if method == "adaptive":
        step = ((hi0 - lo0) / np.float32(num_bins)).astype(np.float32)
        best_err = _affine_error(x, lo0, hi0, levels)
        cur_lo, cur_hi = lo0, hi0
        for _ in range(int(ratio * num_bins)):
            e_lo = _affine_error(x, cur_lo + step, cur_hi, levels)
            e_hi = _affine_error(x, cur_lo, cur_hi - step, levels)
            take_lo = e_lo <= e_hi
            cur_lo = np.where(take_lo, cur_lo + step, cur_lo)
            cur_hi = np.where(take_lo, cur_hi, cur_hi - step)
            cur_err = np.where(take_lo, e_lo, e_hi)
            better = cur_err < best_err
            best_lo = np.where(better, cur_lo, best_lo)
            best_hi = np.where(better, cur_hi, best_hi)
            best_err = np.where(better, cur_err, best_err)
    elif method != "uniform_asym":
        raise ValueError(f"no reference for quantization method {method!r}")
    rng = best_hi - best_lo
    scale = np.where(rng > 0, rng / levels, np.float32(1.0)).astype(np.float32)
    q = np.round((np.clip(x, best_lo[:, None], best_hi[:, None])
                  - best_lo[:, None]) / scale[:, None])
    return (np.clip(q, 0, levels).astype(np.uint8), scale,
            best_lo.astype(np.float32))


def dequantize(codes, scale, zero) -> np.ndarray:
    return (codes.astype(np.float32) * np.asarray(scale, np.float32)[:, None]
            + np.asarray(zero, np.float32)[:, None])


def stored_error(x, bits, method, num_bins, ratio, meta_dtype) -> np.ndarray:
    """Per-row squared error of the reference quantizer on rows ``x`` with
    its scale and zero stored in ``meta_dtype``, as the format keeps them."""
    codes, scale, zero = quantize(x, bits, method, num_bins, ratio)
    md = np.dtype(meta_dtype)
    deq = dequantize(codes, scale.astype(md), zero.astype(md))
    return np.sum(np.square(np.asarray(x, np.float32) - deq), axis=-1)


def unpack_rows(codes: bytes, bits: int, dim: int,
                rows: np.ndarray) -> np.ndarray:
    """Codes of chunk-local rows ``rows`` (uint8 (len(rows), dim)) from a
    packed stream, reading only their bits."""
    buf = np.frombuffer(codes + b"\0\0", np.uint8)
    pos = (rows.astype(np.int64)[:, None] * dim
           + np.arange(dim, dtype=np.int64)[None, :]) * bits
    byte, shift = pos >> 3, (pos & 7).astype(np.uint16)
    two = buf[byte].astype(np.uint16) | (buf[byte + 1].astype(np.uint16) << 8)
    return ((two >> shift) & ((1 << bits) - 1)).astype(np.uint8)


# ------------------------------------------------------------ the store
class StoreView:
    """Read-only view of a ``LocalFSStore`` root, by the format above."""

    def __init__(self, root: str):
        self.root = root

    def steps(self) -> List[int]:
        d = os.path.join(self.root, "manifests")
        out = []
        for name in os.listdir(d) if os.path.isdir(d) else ():
            if name.startswith("ckpt_") and name.endswith(".json"):
                out.append(int(name[5:-5]))
        return sorted(out)

    def manifest(self, step: int) -> dict:
        with open(os.path.join(self.root, "manifests",
                               f"ckpt_{step:012d}.json")) as f:
            return json.load(f)

    def blob(self, key: str) -> bytes:
        with open(os.path.join(self.root, key), "rb") as f:
            return f.read()

    def chain(self, step: int) -> List[dict]:
        """Manifests from the full save ``step`` rests on up to ``step``,
        oldest first, by following ``prev_step``."""
        out = [self.manifest(step)]
        while out[-1]["kind"] != "full":
            out.append(self.manifest(out[-1]["prev_step"]))
        return out[::-1]


def section(data: bytes, ch: dict, name: str) -> bytes:
    o, n = ch["sections"][name]
    return data[o:o + n]


def chunk_row_ids(ch: dict, data: bytes) -> np.ndarray:
    """Global row ids a chunk stores, in its order."""
    if "indices" in ch["sections"]:
        return np.frombuffer(section(data, ch, "indices"),
                             np.uint32).astype(np.int64)
    lo, hi = ch["row_range"]
    return np.arange(lo, hi, dtype=np.int64)


def primary_section(ch: dict) -> str:
    return "codes" if "codes" in ch["sections"] else "values"


def locate(ids: np.ndarray, rows: np.ndarray):
    """(positions in ``rows``, positions in ``ids``) of the rows that
    ``ids`` holds."""
    if not len(ids):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(ids, kind="stable")
    pos = np.minimum(np.searchsorted(ids[order], rows), len(ids) - 1)
    sel = np.nonzero(ids[order][pos] == rows)[0]
    return sel, order[pos[sel]]


def decode_rows(rec: dict, ch: dict, data: bytes, local: np.ndarray):
    """(values f32 (k, dim), scale f32 (k,), row state {aux: (k,)}) of the
    chunk-local rows ``local``, decoded with the reference decoder."""
    md = np.dtype(rec["meta_dtype"])
    scale = np.frombuffer(section(data, ch, "scale"), md)[local]
    zero = np.frombuffer(section(data, ch, "zero"), md)[local]
    codes = unpack_rows(section(data, ch, "codes"), rec["bits"], rec["dim"],
                        local)
    vals = dequantize(codes, scale, zero)
    aux = {}
    for a, dt in rec.get("row_state", {}).items():
        if f"aux:{a}" in ch["sections"]:
            col = np.frombuffer(section(data, ch, f"aux:{a}"), np.dtype(dt))
            aux[a] = col[local]
    return vals, scale.astype(np.float32), aux


def replay_rows(view: StoreView, step: int, table: str,
                rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                           Dict[str, np.ndarray]]:
    """Chain replay for the global rows ``rows`` of one table: the full
    save's values, then each later save overwrites the rows it stored.
    Returns (values, scale, row state) for ``rows``; a row the chain never
    stored raises."""
    rows = np.asarray(rows, np.int64)
    vals: Optional[np.ndarray] = None
    scale = np.zeros(len(rows), np.float32)
    aux: Dict[str, np.ndarray] = {}
    seen = np.zeros(len(rows), bool)
    for man in view.chain(step):
        rec = man["tables"][table]
        if vals is None:
            vals = np.zeros((len(rows), rec["dim"]), np.float32)
        for ch in rec["chunks"]:
            data = view.blob(ch["key"])
            sel, local = locate(chunk_row_ids(ch, data), rows)
            if not len(sel):
                continue
            hit = np.zeros(len(rows), bool)
            hit[sel] = True
            v, s, a = decode_rows(rec, ch, data, local)
            vals[hit], scale[hit], seen[hit] = v, s, True
            for name, col in a.items():
                aux.setdefault(name, np.zeros(len(rows), col.dtype))[hit] = col
    if not seen.all():
        raise ValueError(f"{table}: {int((~seen).sum())} rows never stored")
    return vals, scale, aux


def words_checksum(raw: bytes) -> Tuple[int, int]:
    """(sum, index-weighted sum) of a blob's little-endian uint32 words,
    mod 2**32, zero-padded to a whole word: the host side of the exact
    comparison of dense state with the device."""
    pad = (-len(raw)) % 4
    w = np.frombuffer(raw + b"\0" * pad, "<u4").astype(np.uint64)
    i = np.arange(1, w.size + 1, dtype=np.uint64)
    return (int(np.sum(w, dtype=np.uint64) & _MASK),
            int(np.sum((w * i) & _MASK, dtype=np.uint64) & _MASK))
