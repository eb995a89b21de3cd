"""Jitted public wrappers for checkpoint quantization: Pallas on TPU,
interpret-mode Pallas for validation, jnp elsewhere.

Two generations of API:

* ``adaptive_quant`` — the original unpacked op (codes uint8 + scale/zero);
  kept for compat and as the validation surface for the unpacked kernel.
* ``quant_pack`` / ``quant_codes`` — the fused write path. ``quant_pack``
  returns the packed little-endian word stream (plus per-row scale/zero)
  straight off the device: on TPU via the single fused Pallas kernel, on
  CPU via one jitted quantize dispatch followed by one jitted device-side
  pack dispatch (the packed words — ``bits/8`` bytes per code — are the
  only thing that crosses to the host). ``quant_codes`` runs the SAME
  jitted quantizer but skips the pack, so the host ``pack_bits`` fallback
  path consumes bit-identical codes — that is what makes the fused and
  fallback chunk payloads byte-identical, which the equivalence suite and
  the write-path bench assert.

Both support ``method`` "adaptive" (greedy search, §4.2.3) and
"uniform_asym" (§4.2.1, the search degenerated to zero steps). Both device
paths trace the same ``kernel.quantize_rows``, written to compile to the
same bits under Mosaic and XLA, so on one chip the Pallas and jnp payloads
are byte-identical.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core.quantize import Quantized
from .kernel import (
    adaptive_quant_pallas,
    pack_codes_u32,
    quant_pack_pallas,
    quantize_rows,
)
from .ref import adaptive_quant_ref


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _bucket_rows(rows: int) -> int:
    """Pad row counts to the next power of two (min 256) so ragged
    incremental selections hit a handful of jit cache entries instead of
    compiling per chunk size — on both the jnp and the Pallas path.
    Quantization is row-wise, so zero padding rows are inert and sliced
    off."""
    n = 256
    while n < rows:
        n <<= 1
    return n


@functools.partial(jax.jit, static_argnames=("bits", "num_bins", "ratio",
                                             "block_rows", "impl"))
def adaptive_quant(x: jax.Array, bits: int = 4, num_bins: int = 45,
                   ratio: float = 0.2, block_rows: int = 256,
                   impl: str = "auto") -> Quantized:
    """Row-wise adaptive asymmetric quantization (paper §4.2.3).

    impl: "auto" (pallas on TPU, ref otherwise), "pallas", "interpret", "ref".

    Arbitrary row counts are supported: the kernel requires the grid to tile
    rows exactly, so ragged inputs are zero-padded up to a multiple of the
    block size here and the outputs sliced back — each row quantizes
    independently, so padding rows are inert.
    """
    rows, dim = x.shape
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref" or rows == 0:
        codes, scale, zero = adaptive_quant_ref(x, bits=bits, num_bins=num_bins,
                                                ratio=ratio)
        return Quantized(codes, scale, zero, bits=bits)
    interpret = impl == "interpret"
    br = min(block_rows, _round_up(rows, 8))
    rows_pad = _round_up(rows, br)
    xp = x.astype(jnp.float32)
    if rows_pad != rows:
        xp = jnp.pad(xp, ((0, rows_pad - rows), (0, 0)))
    codes, scale, zero = adaptive_quant_pallas(
        xp, bits=bits, num_bins=num_bins, ratio=ratio,
        block_rows=br, interpret=interpret)
    if rows_pad != rows:
        codes, scale, zero = codes[:rows], scale[:rows], zero[:rows]
    return Quantized(codes, scale, zero, bits=bits)


# ---------------------------------------------------------------------------
# Fused quantize + pack
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PackedQuant:
    """Quantize+pack result.

    ``words``  uint32 device array — the little-endian bit stream of the
               row-padded chunk; its first ``ceil(count*bits/32)`` words
               are the payload (``core.packing.words_to_payload`` cuts the
               exact ``pack_bits`` bytes), the rest are zero padding
    ``scale``  f32 (rows,) host array
    ``zero``   f32 (rows,) host array
    ``count``  number of valid codes (= rows * dim)
    """

    words: jax.Array
    scale: np.ndarray
    zero: np.ndarray
    bits: int
    count: int


def _resolve_steps(method: str, bits: int, num_bins, ratio):
    """→ (num_bins, n_steps); n_steps == 0 means plain uniform asym."""
    if method == "uniform_asym":
        return 1, 0
    if method != "adaptive":
        raise ValueError(f"unsupported fused-quant method {method!r}")
    if num_bins is None:
        num_bins = 45 if bits >= 4 else 25
    if ratio is None:
        ratio = 0.5 if bits <= 2 else 0.2
    return num_bins, int(ratio * num_bins)


def _resolve_impl(impl: str) -> str:
    """"auto" → the fused Pallas kernel on a TPU backend, jnp elsewhere."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return impl


def _pad_rows(x, rows_pad: int):
    """Zero-pad rows to ``rows_pad``: on the host for numpy input, so the
    device sees only bucketed shapes and nothing compiles per chunk."""
    rows = x.shape[0]
    if rows == rows_pad:
        return x
    if isinstance(x, np.ndarray):
        return np.pad(x.astype(np.float32, copy=False),
                      ((0, rows_pad - rows), (0, 0)))
    return jnp.pad(x.astype(jnp.float32), ((0, rows_pad - rows), (0, 0)))


@functools.partial(jax.jit, static_argnames=("bits", "num_bins", "n_steps"))
def _quant_jnp(x, bits: int, num_bins: int, n_steps: int):
    """The jnp device quantizer: the kernel's own :func:`quantize_rows`,
    compiled by XLA."""
    codes, scale, zero = quantize_rows(x.astype(jnp.float32), bits=bits,
                                       num_bins=num_bins, n_steps=n_steps)
    return codes.astype(jnp.uint8), scale[:, 0], zero[:, 0]


@functools.partial(jax.jit, static_argnames=("bits",))
def _pack_jnp(codes, bits: int):
    """codes uint8 (rows, dim), rows*dim % 32 == 0 → uint32 word stream.
    A separate dispatch from ``_quant_jnp`` ON PURPOSE: the fallback path
    reuses the identical compiled quantizer, so packed and host-packed
    payloads can never drift apart through fusion-dependent float rounding."""
    return pack_codes_u32(codes.reshape(-1).astype(jnp.uint32), bits)


def quant_codes(x, *, bits: int, method: str = "adaptive",
                num_bins=None, ratio=None, block_rows: int = 256,
                impl: str = "auto") -> Quantized:
    """The fused-path quantizer WITHOUT the device pack — for the host
    ``pack_bits`` fallback and as the unpacked decode oracle. Codes are
    bit-identical to :func:`quant_pack`'s (same compiled search)."""
    rows, dim = x.shape
    num_bins, n_steps = _resolve_steps(method, bits, num_bins, ratio)
    impl = _resolve_impl(impl)
    if rows == 0:
        z = jnp.zeros((0,), jnp.float32)
        return Quantized(jnp.zeros((0, dim), jnp.uint8), z, z, bits=bits)
    if impl in ("jnp", "ref"):
        xp = _pad_rows(x, _bucket_rows(rows))
        codes, scale, zero = _quant_jnp(xp, bits, num_bins, n_steps)
        return Quantized(codes[:rows], scale[:rows], zero[:rows], bits=bits)
    # pallas/interpret: the fused kernel is the validated artifact, so run
    # it and unpack on the host to stay bit-identical with quant_pack
    pq = quant_pack(x, bits=bits, method=method, num_bins=num_bins,
                    ratio=ratio, block_rows=block_rows, impl=impl)
    from ...core import packing as _packing
    codes = _packing.unpack_bits(
        _packing.words_to_payload(np.asarray(pq.words), pq.count, bits),
        bits, pq.count).reshape(rows, dim)
    return Quantized(jnp.asarray(codes), pq.scale, pq.zero, bits=bits)


def quant_pack(x, *, bits: int, method: str = "adaptive",
               num_bins=None, ratio=None, block_rows: int = 256,
               impl: str = "auto") -> PackedQuant:
    """Fused quantize + bit-pack: (rows, dim) f32 (numpy or device) →
    packed uint32 words on device + per-row scale/zero.

    impl: "auto" (fused Pallas kernel on TPU, jitted jnp elsewhere),
    "pallas", "interpret", "jnp". Rows pad to power-of-two buckets on both
    paths, so ragged incremental chunks share a few compiled programs.
    """
    rows, dim = x.shape
    num_bins, n_steps = _resolve_steps(method, bits, num_bins, ratio)
    count = rows * dim
    impl = _resolve_impl(impl)

    if count == 0:
        z = np.zeros((0,), np.float32)
        return PackedQuant(jnp.zeros((0,), jnp.uint32), z, z, bits, 0)
    # _bucket_rows pads to a power of two ≥ 256, so the padded code stream
    # splits into whole 32-code groups and whole kernel blocks
    rows_pad = _bucket_rows(rows)
    xp = _pad_rows(x, rows_pad)
    if impl in ("jnp", "ref"):
        codes, scale, zero = _quant_jnp(xp, bits, num_bins, n_steps)
        words = _pack_jnp(codes, bits)
    else:
        words, scale, zero = quant_pack_pallas(
            xp, bits=bits, num_bins=num_bins, n_steps=n_steps,
            block_rows=min(block_rows, rows_pad),
            interpret=impl == "interpret")
    return PackedQuant(words, np.asarray(scale)[:rows],
                       np.asarray(zero)[:rows], bits, count)
