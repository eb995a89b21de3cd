"""Decode-equivalence suite for the fused quantize+pack path.

The wire-format contract: for every bit width 1–8 and both checkpoint
methods (adaptive, uniform_asym), the fused op's device-packed payload must
be byte-identical to packing the SAME quantizer's codes through the
original host ``pack_bits_reference`` oracle — including ragged last
chunks — and must restore byte-identically through the unchanged
``unpack_bits`` decode path. The host fallback stays selectable on the
manager (``fused_pack=False``) and must produce byte-identical checkpoints.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    CheckNRunManager,
    CheckpointConfig,
    InMemoryStore,
    QuantConfig,
    Snapshot,
)
from repro.core import packing
from repro.kernels.adaptive_quant import quant_codes, quant_pack

RNG = np.random.default_rng(7)


def _rows(rows, dim):
    return jnp.asarray((RNG.normal(size=(rows, dim)) *
                        RNG.gamma(1.0, 1.0, (rows, 1))).astype(np.float32))


@pytest.mark.parametrize("method", ["adaptive", "uniform_asym"])
@pytest.mark.parametrize("bits", list(range(1, 9)))
def test_fused_payload_matches_host_reference(method, bits):
    """Device-packed words == pack_bits_reference of the same codes, and
    both decode to the same values."""
    x = _rows(1000, 64)  # ragged vs the 256-row jit bucket
    pq = quant_pack(x, bits=bits, method=method, impl="jnp")
    q = quant_codes(x, bits=bits, method=method, impl="jnp")
    host = packing.pack_bits_reference(np.asarray(q.codes), bits)
    dev = packing.words_to_payload(np.asarray(pq.words), pq.count, bits)
    assert dev == host
    np.testing.assert_array_equal(np.asarray(pq.scale), np.asarray(q.scale))
    np.testing.assert_array_equal(np.asarray(pq.zero), np.asarray(q.zero))
    back = packing.unpack_bits(dev, bits, pq.count).reshape(x.shape)
    np.testing.assert_array_equal(back, np.asarray(q.codes))


@pytest.mark.parametrize("rows,dim", [(37, 10), (256, 128), (513, 200),
                                      (1, 64), (31, 3)])
def test_fused_payload_ragged_shapes(rows, dim):
    """Ragged row counts and non-lane-aligned dims — the jit row bucket and
    the word-stream truncation must never leak padding into the payload."""
    x = _rows(rows, dim)
    for bits in (1, 3, 4, 7, 8):
        pq = quant_pack(x, bits=bits, method="adaptive", impl="jnp")
        q = quant_codes(x, bits=bits, method="adaptive", impl="jnp")
        assert pq.count == rows * dim
        dev = packing.words_to_payload(np.asarray(pq.words), pq.count, bits)
        assert len(dev) == packing.packed_nbytes(rows * dim, bits)
        assert dev == packing.pack_bits_reference(np.asarray(q.codes), bits)


@pytest.mark.parametrize("method", ["adaptive", "uniform_asym"])
@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
def test_fused_kernel_interpret_matches_jnp(method, bits):
    """The Pallas fused kernel (interpret mode) and the jnp device path
    trace the same quantizer and emit the same word layout: payloads and
    scale/zero must be byte-identical (the on-chip smoke asserts the same
    between the compiled kernel and XLA)."""
    x = _rows(256, 64)
    pk = quant_pack(x, bits=bits, method=method, impl="interpret")
    pj = quant_pack(x, bits=bits, method=method, impl="jnp")
    assert (packing.words_to_payload(np.asarray(pk.words), pk.count, bits)
            == packing.words_to_payload(np.asarray(pj.words), pj.count, bits))
    assert pk.scale.tobytes() == pj.scale.tobytes()
    assert pk.zero.tobytes() == pj.zero.tobytes()


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
@pytest.mark.parametrize("dim", [64, 32, 128])
def test_pack_row_words_matches_stream_packer(bits, dim):
    """The kernel's in-VMEM row packer (two bf16 MXU products, no lane
    reshape) emits exactly the stream packer's words wherever rows own
    whole words — including codes that straddle 16-bit halves."""
    from repro.kernels.adaptive_quant.kernel import (pack_codes_u32,
                                                     pack_row_words,
                                                     row_pack_weights)
    codes = RNG.integers(0, 1 << bits, size=(96, dim)).astype(np.int32)
    codes[0] = (1 << bits) - 1          # all-ones row: every bit set
    w_low, w_high = row_pack_weights(dim, bits)
    rows = np.asarray(pack_row_words(jnp.asarray(codes), bits, w_low,
                                     w_high)).view(np.uint32)
    stream = np.asarray(pack_codes_u32(
        jnp.asarray(codes.reshape(-1).astype(np.uint32)), bits))
    np.testing.assert_array_equal(rows.reshape(-1), stream)
    assert (packing.words_to_payload(stream, codes.size, bits)
            == packing.pack_bits_reference(codes.astype(np.uint8), bits))


def test_recip_is_accurate():
    """The divide-free reciprocal the quantizer uses in both compilers."""
    from repro.kernels.adaptive_quant.kernel import _recip
    y = np.concatenate([np.geomspace(1e-30, 1e30, 4001),
                        RNG.uniform(0.5, 2.0, 1000)]).astype(np.float32)
    got = np.asarray(_recip(jnp.asarray(y)))
    np.testing.assert_allclose(got, 1.0 / y.astype(np.float64), rtol=3e-7)


def test_fused_kernel_interpret_ragged_blocks():
    """Rows that don't tile the kernel block (and a ragged dim): padding
    rows/lanes must not corrupt the packed stream."""
    x = _rows(70, 40)
    for bits in (3, 4):
        pk = quant_pack(x, bits=bits, method="uniform_asym", impl="interpret")
        pj = quant_pack(x, bits=bits, method="uniform_asym", impl="jnp")
        assert pk.count == pj.count == 70 * 40
        bk = packing.words_to_payload(np.asarray(pk.words), pk.count, bits)
        bj = packing.words_to_payload(np.asarray(pj.words), pj.count, bits)
        # uniform_asym has no search, so interpret and jnp agree exactly
        assert bk == bj


def _snap(rows=5000, dim=16):
    table = (RNG.normal(size=(rows, dim)) *
             RNG.gamma(1.0, 1.0, (rows, 1))).astype(np.float32)
    acc = np.abs(RNG.normal(size=rows)).astype(np.float32)
    return Snapshot(step=1, tables={"emb": table},
                    row_state={"emb": {"acc": acc}},
                    touched={"emb": np.ones(rows, bool)},
                    dense={"w": np.arange(16, dtype=np.float32).reshape(4, 4)},
                    extra={})


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
def test_manager_fused_vs_host_fallback_byte_identical(bits):
    """End to end through the manager: fused device packing and the host
    pack_bits fallback must write byte-identical chunk blobs (ragged last
    chunk included) and restore byte-identically."""
    snap = _snap(rows=5000)  # 5000 % 700 != 0 → ragged last chunk
    qcfg = QuantConfig(bits=bits, method="adaptive")

    def run(fused):
        store = InMemoryStore()
        mgr = CheckNRunManager(store, CheckpointConfig(
            policy="full_only", quant=qcfg, async_write=False,
            chunk_rows=700, fused_pack=fused))
        mgr.save(snap).result()
        rs = mgr.restore()
        mgr.close()
        return store, rs

    s_fused, rs_fused = run(True)
    s_host, rs_host = run(False)
    keys = list(s_fused.list("chunks/"))
    assert keys == list(s_host.list("chunks/")) and len(keys) >= 8
    for k in keys:
        assert s_fused.get(k) == s_host.get(k), k
    np.testing.assert_array_equal(rs_fused.tables["emb"],
                                  rs_host.tables["emb"])
    np.testing.assert_array_equal(rs_fused.row_state["emb"]["acc"],
                                  rs_host.row_state["emb"]["acc"])


def test_manager_incremental_fused_vs_fallback():
    """Incremental (index-carrying, non-contiguous) chunks through both
    pack paths: byte-identical blobs."""
    rows = 3000
    snap = _snap(rows=rows)
    touched = np.zeros(rows, bool)
    touched[RNG.choice(rows, 700, replace=False)] = True

    def run(fused):
        store = InMemoryStore()
        mgr = CheckNRunManager(store, CheckpointConfig(
            policy="one_shot", quant=QuantConfig(bits=4, method="adaptive"),
            async_write=False, chunk_rows=512, fused_pack=fused))
        mgr.save(snap).result()
        inc = Snapshot(step=2, tables=snap.tables, row_state=snap.row_state,
                       touched={"emb": touched.copy()}, dense=snap.dense,
                       extra={})
        mgr.save(inc).result()
        mgr.close()
        return store

    s_fused, s_host = run(True), run(False)
    from repro.core import manifest as mf
    prefix = mf.chunk_prefix(2)
    keys = list(s_fused.list(prefix))
    assert keys == list(s_host.list(prefix)) and keys
    for k in keys:
        assert s_fused.get(k) == s_host.get(k), k
