"""Jitted public wrapper for the on-device chunk content hash.

``chunk_hash32_device(words)`` hashes the packed uint32 word stream that
``quant_pack`` just produced, without the codes ever leaving the device:
Pallas kernel on TPU, one jitted jnp dispatch elsewhere, numpy reference
under ``impl="ref"``. The result equals ``ref.chunk_hash32`` of the
serialized payload bytes (``core.packing.words_to_payload``) because the
packed stream's tail bits beyond the payload are zero — the byte
equivalence ``tests/test_chunk_hash.py`` pins for bits 1–8 × both quant
methods.

The words are hashed as they come, padding included: the valid count is
a traced argument and the words past it are masked out inside the hash,
so the bucketed word arrays ``quant_pack`` emits share a few compiled
programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import chunk_hash_pallas, finalize, mix_terms
from .ref import chunk_hash32, hash_words_np


@jax.jit
def _hash_words_jnp(words_pad: jax.Array, count: jax.Array) -> jax.Array:
    i = jnp.arange(words_pad.shape[0], dtype=jnp.uint32)
    t = mix_terms(words_pad, i)
    t = jnp.where(i < count, t, jnp.uint32(0))
    return finalize(jnp.sum(t, dtype=jnp.uint32), count)


def chunk_hash32_device(words, count=None, impl: str = "auto") -> int:
    """Hash ``words[:count]`` (uint32 stream) on device; returns the Python
    int hash. ``impl``: "auto" (pallas on TPU, jnp elsewhere), "pallas",
    "interpret", "jnp", "ref"."""
    n = int(words.shape[0]) if count is None else int(count)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "ref" or n == 0:
        return hash_words_np(np.asarray(words)[:n])
    if impl == "jnp":
        return int(_hash_words_jnp(jnp.asarray(words, jnp.uint32),
                                   jnp.uint32(n)))
    return int(chunk_hash_pallas(words, n, interpret=impl == "interpret"))


@functools.lru_cache(maxsize=None)
def _impl_for(quant_impl: str) -> str:
    """Map the manager's ``quant_impl`` knob onto a hash impl: the hash
    should run wherever quantization ran ("ref" quantization is a host
    path, so its hash is too)."""
    return {"auto": "auto", "pallas": "pallas", "interpret": "interpret",
            "jnp": "jnp", "ref": "ref"}.get(quant_impl, "auto")


__all__ = ["chunk_hash32", "chunk_hash32_device", "hash_words_np",
           "_impl_for"]
