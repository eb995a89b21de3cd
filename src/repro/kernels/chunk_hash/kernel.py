"""Pallas TPU kernel: 32-bit content hash of a packed chunk word stream.

Runs alongside ``quant_pack`` on the write path so the hash is computed
over the SAME device words the host serializes — an end-to-end integrity
witness from the accelerator's VMEM to the object store (the host-side
crc32 only covers the payload after it crossed PCIe/host memory).

Mapping: the word stream is viewed as (rows, 128) uint32 lanes; the grid
tiles rows into (BLOCK_ROWS, 128) VMEM blocks. Each block computes the
masked partial sums of the per-word mixed terms (see ``ref.py`` — the terms
are position-folded, so the order-sensitive hash still reduces through an
associative sum and blocks are independent), folded to one (8, 128) tile
per block. The wrapper sums the tiles mod 2^32 and applies the final
avalanche. One HBM read of the words, 4 KiB written back per block.

The valid word count rides in through scalar prefetch (SMEM) rather than
as a static closure constant, so ragged chunk tails don't fan out into one
compiled kernel per length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import PRIME1, PRIME2, PRIME3, PRIME5

LANES = 128


def mix_terms(words: jax.Array, index: jax.Array) -> jax.Array:
    """Per-word mixed terms, uint32 wraparound — must match
    ``ref.mix_terms_np`` bit-for-bit (jnp uint32 arithmetic wraps, like
    numpy's)."""
    t = words + index * jnp.uint32(PRIME2)
    t = t ^ (t >> jnp.uint32(15))
    t = t * jnp.uint32(PRIME1)
    t = t ^ (t >> jnp.uint32(13))
    t = t * jnp.uint32(PRIME3)
    return t


def finalize(acc: jax.Array, count: jax.Array) -> jax.Array:
    """Length fold + avalanche, uint32 — must match ``ref.finalize``."""
    h = acc + count * jnp.uint32(PRIME5)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(PRIME1)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(PRIME3)
    h = h ^ (h >> jnp.uint32(16))
    return h


def chunk_hash_kernel(n_ref, w_ref, out_ref, *, block_rows: int):
    """One grid block's masked partial sums of mixed terms.

    n_ref (1,) int32 in SMEM — the valid word count
    w_ref (BLOCK_ROWS, 128) uint32 — this block's slice of the word stream
    out_ref (1, 8, 128) int32 — the block's partials, one per (sublane,
    lane); int32 because Mosaic reduces no unsigned type, and the bits of
    a wrapping sum are the same either way
    """
    b = pl.program_id(0)
    w = w_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    idx = b * (block_rows * LANES) + row * LANES + col
    t = mix_terms(w, idx.astype(jnp.uint32))
    t = jnp.where(idx < n_ref[0], t, jnp.uint32(0))
    t = jax.lax.bitcast_convert_type(t, jnp.int32)
    out_ref[...] = t.reshape(block_rows // 8, 8, LANES).sum(axis=0)[None]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chunk_hash_pallas(words: jax.Array, count, block_rows: int = 0,
                      interpret: bool = False) -> jax.Array:
    """Hash a uint32 word stream on device via the Pallas kernel; returns
    the uint32 hash scalar. ``words`` may be zero-padded past ``count`` —
    padding words are masked out, so the result equals
    ``ref.hash_words_np(words[:count])``. ``block_rows`` (a multiple of 8;
    0 → up to 512 rows, 64 Ki words, per block) sets the grid tiling."""
    words = jnp.asarray(words, jnp.uint32)
    n = words.shape[0]
    if not block_rows:
        block_rows = min(512, max(8, -(-n // (8 * LANES)) * 8))
    per_block = block_rows * LANES
    n_pad = max(per_block, -(-n // per_block) * per_block)
    if n_pad != n:
        words = jnp.pad(words, (0, n_pad - n))
    w2d = words.reshape(-1, LANES)
    num_blocks = w2d.shape[0] // block_rows
    count = jnp.asarray(count, jnp.uint32)
    kernel = functools.partial(chunk_hash_kernel, block_rows=block_rows)
    partials = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_blocks,),
            in_specs=[pl.BlockSpec((block_rows, LANES), lambda i, n: (i, 0))],
            out_specs=pl.BlockSpec((1, 8, LANES), lambda i, n: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks, 8, LANES), jnp.int32),
        interpret=interpret,
    )(count.astype(jnp.int32).reshape(1), w2d)
    partials = jax.lax.bitcast_convert_type(partials, jnp.uint32)
    return finalize(jnp.sum(partials, dtype=jnp.uint32), count)
