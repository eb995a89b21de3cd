"""The benchmark's yardstick: chip peaks, analytic model FLOPs and the
bytes each checkpoint kernel must move.

Kept with the benchmark, so that a change to the program is read against
the same work. Nothing here imports the program.
"""

from __future__ import annotations

import math

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to PEAKS with its source") from None


# --------------------------------------------------------------- model FLOPs
def _mlp_flops(dims) -> float:
    return float(sum(2 * a * b for a, b in zip(dims[:-1], dims[1:])))


def dlrm_forward_flops(cfg: dict) -> float:
    """Forward FLOPs per example of DLRM: bottom MLP, the pairwise dot
    interaction over the dense vector and the sparse fields, top MLP."""
    f = _mlp_flops([cfg["n_dense"]] + cfg["bot_mlp"])
    n_feat = len(cfg["vocab_sizes"]) + 1
    f += 2.0 * n_feat * n_feat * cfg["embed_dim"]
    n_inter = n_feat * (n_feat - 1) // 2
    f += _mlp_flops([cfg["embed_dim"] + n_inter] + cfg["top_mlp"])
    return f


def xdeepfm_forward_flops(cfg: dict) -> float:
    """Forward FLOPs per example of xDeepFM: the CIN's outer products and
    compressions, and the deep MLP over the flattened embeddings."""
    n_f, d = len(cfg["vocab_sizes"]), cfg["embed_dim"]
    f, h_prev = 0.0, n_f
    for h in cfg["cin_layers"]:
        f += 2.0 * h_prev * n_f * d          # outer product
        f += 2.0 * h * h_prev * n_f * d      # compression
        h_prev = h
    f += _mlp_flops([n_f * d] + cfg["mlp"] + [1])
    return f


FORWARD_FLOPS = {"dlrm-rm2": dlrm_forward_flops,
                 "xdeepfm": xdeepfm_forward_flops}


def train_step_flops(cfg: dict) -> float:
    """Model FLOPs of one train step: three times the forward pass (forward
    plus backward), over the configuration's batch."""
    return 3.0 * FORWARD_FLOPS[cfg["arch"]](cfg) * cfg["batch"]


# ------------------------------------------------------------- kernel bytes
def quant_pack_bytes(rows: int, dim: int, bits: int) -> int:
    """Bytes ``quant_pack`` must move for ``rows`` real rows of ``dim``:
    the f32 rows read once, the packed code words and the f32 scale and
    zero per row written once. Padding rows of the power-of-two bucket are
    not counted, so padding shows as a lower roofline share."""
    words = math.ceil(rows * dim * bits / 32)
    return rows * dim * 4 + words * 4 + rows * 8


def chunk_hash_bytes(payload_bytes: int) -> int:
    """Bytes the chunk hash must read: the payload's words, once."""
    return math.ceil(payload_bytes / 4) * 4


def save_device_bytes(selected_rows_by_dim: dict, payload_bytes: int) -> int:
    """The least bytes the chip must move for one save: the f32 bytes of
    every selected row read once, plus the payload written once.
    ``selected_rows_by_dim`` maps a row width to the rows selected at it."""
    read = sum(rows * dim * 4 for dim, rows in selected_rows_by_dim.items())
    return read + payload_bytes


KERNEL_BYTES = {"quant_pack_pallas": quant_pack_bytes,
                "chunk_hash_pallas": chunk_hash_bytes}
