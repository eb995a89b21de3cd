"""Pallas TPU kernels: row-wise checkpoint quantization (Check-N-Run §4.2.3)
— the paper's checkpoint-optimization hot loop (must finish a terabyte-model
quantization inside a 5-minute budget).

Two kernels share the greedy range search:

* ``adaptive_quant_kernel`` — the original: emits unpacked uint8 codes, the
  host packs bits at serialization (kept for compat + as the unpacked
  oracle).
* ``quant_pack_kernel`` — the fused write path: one kernel emits the packed
  little-endian bit stream as uint32 words plus per-row scale/zero, so the
  host encode stage shrinks to header assembly and the HBM→host transfer
  carries ``bits/8`` bytes per code instead of a full uint8. ``n_steps=0``
  degrades the search to plain uniform asymmetric quantization (§4.2.1), so
  one kernel serves both checkpoint methods.

TPU mapping: rows tile into (BLOCK_ROWS, dim) VMEM blocks; the greedy
min/max search runs as a fori loop of VPU ops entirely in VMEM, one pass per
candidate shrink, so HBM traffic is exactly one read of the table + one
write of the packed words/scales. The fused kernel's error evaluation works
in normalized ``r = (x - lo) * inv_scale`` space (err = scale² · Σ (r -
round(clip(r)))²): one multiply replaces the per-element divide and the
dequantize round-trip of the textbook formulation.

The fused quantizer (:func:`quantize_rows`) is ONE function traced by both
the Pallas kernel and the jnp device path in ``ops.py``, written so that
Mosaic and XLA compile it to the same IEEE operations: the reciprocal is a
bit-trick seed plus Newton steps (no compiler-specific divide), and the
per-row error sum is taken over fixed-point integers, whose sum does not
depend on the reduction order either compiler picks. The two paths
therefore emit byte-identical payloads on the same chip.

Packing layout: code ``p`` of the flat row-major code stream sits at stream
bit ``bits·p`` — exactly the wire format of ``core.packing.pack_bits``, so
``words.tobytes()`` (little-endian) is byte-identical to the host packer
and decodes through the unchanged ``unpack_bits`` oracle. Where every row
owns whole words (``dim·bits % 32 == 0``, e.g. dim 64 at any width) the
kernel packs each row in VMEM with two small MXU products and no lane
reshape (:func:`pack_row_words`); other widths emit int32 codes that the
wrapper packs with :func:`pack_codes_u32` in the same jitted program.

Grids: (rows // BLOCK_ROWS,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _quant_err(x, x_min, x_max, levels, valid=None):
    """Per-row squared-l2 error for candidate range [x_min, x_max];
    lane-padding columns are masked out of the sum."""
    rng = x_max - x_min
    scale = jnp.where(rng > 0, rng / levels, 1.0)
    xc = jnp.clip(x, x_min, x_max)
    q = jnp.round((xc - x_min) / scale)
    q = jnp.clip(q, 0.0, levels)
    deq = q * scale + x_min
    err = jnp.square(x - deq)
    if valid is not None:
        err = jnp.where(valid, err, 0.0)
    return jnp.sum(err, axis=-1, keepdims=True)


def adaptive_quant_kernel(x_ref, codes_ref, scale_ref, zero_ref, *,
                          bits: int, num_bins: int, ratio: float,
                          valid_dim: int):
    x = x_ref[...].astype(jnp.float32)  # (BLOCK_ROWS, DIM_PAD) in VMEM
    levels = float((1 << bits) - 1)

    dim_pad = x.shape[-1]
    if valid_dim != dim_pad:
        # mask lane padding out of min/max/error computations
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        valid = lane < valid_dim
        big = jnp.float32(3.4e38)
        x_min0 = jnp.min(jnp.where(valid, x, big), axis=-1, keepdims=True)
        x_max0 = jnp.max(jnp.where(valid, x, -big), axis=-1, keepdims=True)
    else:
        valid = None
        x_min0 = jnp.min(x, axis=-1, keepdims=True)
        x_max0 = jnp.max(x, axis=-1, keepdims=True)

    step = (x_max0 - x_min0) / num_bins
    n_steps = int(ratio * num_bins)

    err0 = _quant_err(x, x_min0, x_max0, levels, valid)

    def body(_, carry):
        cur_min, cur_max, best_min, best_max, best_err = carry
        err_lo = _quant_err(x, cur_min + step, cur_max, levels, valid)
        err_hi = _quant_err(x, cur_min, cur_max - step, levels, valid)
        take_lo = err_lo <= err_hi
        new_min = jnp.where(take_lo, cur_min + step, cur_min)
        new_max = jnp.where(take_lo, cur_max, cur_max - step)
        cur_err = jnp.where(take_lo, err_lo, err_hi)
        improve = cur_err < best_err
        best_min = jnp.where(improve, new_min, best_min)
        best_max = jnp.where(improve, new_max, best_max)
        best_err = jnp.where(improve, cur_err, best_err)
        return cur_min * 0 + new_min, new_max, best_min, best_max, best_err

    init = (x_min0, x_max0, x_min0, x_max0, err0)
    _, _, best_min, best_max, _ = jax.lax.fori_loop(0, n_steps, body, init)

    rng = best_max - best_min
    scale = jnp.where(rng > 0, rng / levels, 1.0)
    q = jnp.round((jnp.clip(x, best_min, best_max) - best_min) / scale)
    codes_ref[...] = jnp.clip(q, 0.0, levels).astype(jnp.uint8)
    scale_ref[...] = scale[:, 0]
    zero_ref[...] = best_min[:, 0]


def adaptive_quant_pallas(x: jax.Array, *, bits: int, num_bins: int,
                          ratio: float, block_rows: int = 256,
                          interpret: bool = False):
    """x (rows, dim) f32 → (codes u8 (rows, dim), scale (rows,), zero (rows,)).

    rows must divide block_rows; dim is padded to 128 lanes internally.
    """
    rows, dim = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    dim_pad = ((dim + 127) // 128) * 128
    if dim_pad != dim:
        x = jnp.pad(x, ((0, 0), (0, dim_pad - dim)))

    grid = (rows // block_rows,)
    kernel = functools.partial(adaptive_quant_kernel, bits=bits,
                               num_bins=num_bins, ratio=ratio, valid_dim=dim)
    codes, scale, zero = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, dim_pad), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, dim_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, dim_pad), jnp.uint8),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return codes[:, :dim], scale, zero


# ---------------------------------------------------------------------------
# Fused quantize + bit-pack kernel
# ---------------------------------------------------------------------------

# a row whose range is at most this is stored with scale 1 (all codes 0,
# dequantized to its min): keeps every scale a normal float, so the
# reciprocal below needs no special cases
_TINY_RANGE = 1e-30


def _recip(y):
    """1/y for positive normal f32 ``y`` from IEEE multiplies and subtracts
    only: a bit-trick seed (≈12% error) and four Newton steps. A divide is
    lowered differently by Mosaic and by XLA; these operations are not."""
    seed = jnp.int32(0x7EF311C3) - jax.lax.bitcast_convert_type(y, jnp.int32)
    r = jax.lax.bitcast_convert_type(seed, jnp.float32)
    for _ in range(4):
        r = r * (2.0 - y * r)
    return r


def _scale_of(rng, levels: float):
    return jnp.where(rng > _TINY_RANGE, rng * (1.0 / levels), 1.0)


def err_frac_bits(bits: int, dim: int, num_bins: int, n_steps: int) -> int:
    """Fraction bits of the fixed-point squared error, as many as keep a
    row's sum inside int32. A candidate range keeps at least ``1 - f`` of
    the row's range (``f = n_steps / num_bins``), so a clipped element's
    normalized error is below ``levels·f/(1-f) + ½``."""
    levels = (1 << bits) - 1
    f = n_steps / num_bins
    dmax = levels * f / (1.0 - f) + 0.5
    bound = dim * dmax * dmax
    return int(max(0, min(24, np.floor(np.log2((2 ** 31 - 1) / bound)))))


def quantize_rows(x, *, bits: int, num_bins: int, n_steps: int):
    """Row-wise asymmetric quantization with the greedy range search in
    normalized r-space (``n_steps=0`` → the full [min, max] range, plain
    uniform asymmetric). x (rows, dim) f32 → (codes int32 (rows, dim),
    scale (rows, 1), zero (rows, 1)).

    Traced by the Pallas kernel and by the jnp path alike; see the module
    docstring for why both compile it to the same bits."""
    levels = float((1 << bits) - 1)
    x_min0 = jnp.min(x, axis=-1, keepdims=True)
    x_max0 = jnp.max(x, axis=-1, keepdims=True)
    best_min, best_max = x_min0, x_max0
    if n_steps:
        step = (x_max0 - x_min0) * (1.0 / num_bins)
        unit = float(2 ** err_frac_bits(bits, x.shape[-1], num_bins,
                                        n_steps))

        def err_of(lo, hi):
            scale = _scale_of(hi - lo, levels)
            r = (x - lo) * _recip(scale)
            d = r - jnp.round(jnp.clip(r, 0.0, levels))
            e = jnp.round(d * d * unit).astype(jnp.int32)
            s = jnp.sum(e, axis=-1, keepdims=True).astype(jnp.float32)
            return scale * scale * s

        def body(_, carry):
            cur_min, cur_max, best_min, best_max, best_err = carry
            err_lo = err_of(cur_min + step, cur_max)
            err_hi = err_of(cur_min, cur_max - step)
            take_lo = err_lo <= err_hi
            new_min = jnp.where(take_lo, cur_min + step, cur_min)
            new_max = jnp.where(take_lo, cur_max, cur_max - step)
            cur_err = jnp.where(take_lo, err_lo, err_hi)
            improve = cur_err < best_err
            best_min = jnp.where(improve, new_min, best_min)
            best_max = jnp.where(improve, new_max, best_max)
            best_err = jnp.where(improve, cur_err, best_err)
            return new_min, new_max, best_min, best_max, best_err

        init = (x_min0, x_max0, x_min0, x_max0, err_of(x_min0, x_max0))
        _, _, best_min, best_max, _ = jax.lax.fori_loop(0, n_steps, body,
                                                        init)
    scale = _scale_of(best_max - best_min, levels)
    r = (x - best_min) * _recip(scale)
    codes = jnp.round(jnp.clip(r, 0.0, levels)).astype(jnp.int32)
    return codes, scale, best_min


def pack_codes_u32(codes: jax.Array, bits: int) -> jax.Array:
    """Bit-pack a flat uint32 code array (size % 32 == 0) into the
    little-endian word stream: code ``p`` occupies stream bits
    ``[bits*p, bits*(p+1))``. The jnp device path's packer, and the Pallas
    wrapper's for widths where rows do not own whole words."""
    g = codes.reshape(-1, 32)
    ngroups = g.shape[0]
    cols = [jnp.zeros((ngroups,), jnp.uint32) for _ in range(bits)]
    for j in range(32):
        bitpos = bits * j
        wi, sh = bitpos >> 5, bitpos & 31
        cols[wi] = cols[wi] | (g[:, j] << sh)
        if sh + bits > 32:
            cols[wi + 1] = cols[wi + 1] | (g[:, j] >> (32 - sh))
    return jnp.stack(cols, axis=1).reshape(-1)


def row_pack_weights(dim: int, bits: int):
    """Constant matrices for :func:`pack_row_words` (``dim·bits % 32 ==
    0``). Code ``j`` of a row starts at row bit ``o = bits·j``, in 16-bit
    half ``h = o >> 4`` at shift ``s = o & 15``; the part of it that spills
    past that half (``bits > 16 - s``) lands at bit 0 of half ``h + 1``.
    Column ``w`` collects the low half of word ``w``, column ``wp + w`` its
    high half (``wp`` = words per row rounded up to 128 lanes). Returns
    (w_low, w_high) bf16 (dim, 2·wp); ``w_high`` is None when no code
    crosses a half (bits 1, 2, 4, 8)."""
    wpr = dim * bits // 32
    wp = -(-wpr // 128) * 128
    w_low = np.zeros((dim, 2 * wp), np.float32)
    w_high = np.zeros((dim, 2 * wp), np.float32)
    for j in range(dim):
        o = bits * j
        h, s = o >> 4, o & 15
        w_low[j, (h >> 1) + (h & 1) * wp] = 2.0 ** s
        if s + bits > 16:
            w_high[j, ((h + 1) >> 1) + ((h + 1) & 1) * wp] = 1.0
    if 16 % bits == 0:
        w_high = None
    return (jnp.asarray(w_low, jnp.bfloat16),
            None if w_high is None else jnp.asarray(w_high, jnp.bfloat16))


def pack_row_words(codes, bits: int, w_low, w_high):
    """codes int32 (rows, dim), rows owning whole words → int32 words
    (rows, dim·bits/32) in the ``pack_bits`` wire format, with no reshape
    across lanes. Each code splits into the part inside its 16-bit half
    and the part that spills into the next; both go through a bf16 MXU
    product with powers of two. Every operand is exact in bf16 (codes <
    2^8, weights ≤ 2^15) and every column sums disjoint bit fields below
    2^16, so the f32 accumulation is exact in any order."""
    dim = codes.shape[-1]
    wpr = dim * bits // 32
    wp = w_low.shape[-1] // 2
    if w_high is None:
        low = codes
    else:
        o = bits * jax.lax.broadcasted_iota(jnp.int32, (1, dim), 1)
        low_bits = jnp.minimum(bits, 16 - (o & 15))
        high = codes >> low_bits
        low = codes - (high << low_bits)
    halves = jnp.dot(low.astype(jnp.float32).astype(jnp.bfloat16), w_low,
                     preferred_element_type=jnp.float32)
    if w_high is not None:
        halves = halves + jnp.dot(
            high.astype(jnp.float32).astype(jnp.bfloat16), w_high,
            preferred_element_type=jnp.float32)
    lo16 = halves[:, :wp].astype(jnp.int32)
    hi16 = halves[:, wp:].astype(jnp.int32)
    return (lo16 | (hi16 << 16))[:, :wpr]


def quant_pack_kernel(x_ref, *refs, bits: int, num_bins: int, n_steps: int):
    """One (BLOCK_ROWS, dim) block: quantize, then pack rows into words
    (with the two weight operands) or emit the int32 codes."""
    *w_refs, out_ref, scale_ref, zero_ref = refs
    codes, scale, zero = quantize_rows(x_ref[...], bits=bits,
                                       num_bins=num_bins, n_steps=n_steps)
    if w_refs:
        w_low = w_refs[0][...]
        w_high = w_refs[1][...] if len(w_refs) > 1 else None
        out_ref[...] = pack_row_words(codes, bits, w_low, w_high)
    else:
        out_ref[...] = codes
    scale_ref[...] = scale
    zero_ref[...] = zero


@functools.partial(jax.jit, static_argnames=("bits", "num_bins", "n_steps",
                                             "block_rows", "interpret"))
def quant_pack_pallas(x: jax.Array, *, bits: int, num_bins: int,
                      n_steps: int, block_rows: int = 256,
                      interpret: bool = False):
    """x (rows, dim) f32 → (packed u32 (rows*dim*bits//32,), scale (rows,),
    zero (rows,)), one compiled program per (shape, static args).

    rows must be a multiple of block_rows, and block_rows of 32 so every
    grid block emits whole words (the wrapper in ``ops.py`` guarantees
    both)."""
    rows, dim = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    assert block_rows % 32 == 0, block_rows
    x = x.astype(jnp.float32)
    row_words = (dim * bits) % 32 == 0
    width = dim * bits // 32 if row_words else dim
    operands = [x]
    in_specs = [pl.BlockSpec((block_rows, dim), lambda i: (i, 0))]
    if row_words:
        for w in row_pack_weights(dim, bits):
            if w is not None:
                operands.append(w)
                in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
    kernel = functools.partial(quant_pack_kernel, bits=bits,
                               num_bins=num_bins, n_steps=n_steps)
    out, scale, zero = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    out = jax.lax.bitcast_convert_type(out, jnp.uint32).reshape(-1)
    words = out if row_words else pack_codes_u32(out, bits)
    return words, scale[:, 0], zero[:, 0]
