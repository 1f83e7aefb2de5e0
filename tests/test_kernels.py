"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st  # noqa: E501

from repro.kernels.decode_attention import kernel as DK
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm_chunk.ops import mlstm_chunk
from repro.kernels.mlstm_chunk.ref import mlstm_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 5e-5


# ---------------------------------------------------------------------------
# flash attention sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hkv,hd", [
    (1, 64, 4, 4, 32),     # MHA
    (2, 128, 8, 2, 64),    # GQA 4x
    (1, 96, 6, 1, 32),     # MQA, non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, s, h, hkv, hd, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_local_window(window):
    b, s, h, hd = 1, 128, 2, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_kv=32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_flash_attention_gradients_match_ref():
    b, s, h, hkv, hd = 1, 64, 4, 2, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, hd)), jnp.float32)

    def lk(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_kv=32) ** 2)

    def lr(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3)


def test_flash_attention_softcap():
    b, s, h, hd = 1, 64, 2, 32
    q = jnp.asarray(RNG.normal(0, 2, (b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 2, (b, s, h, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, h, hd)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, softcap=10.0,
                          block_q=32, block_kv=32)
    ref = attention_ref(q, k, v, causal=True, softcap=10.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


# ---------------------------------------------------------------------------
# decode attention sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,hd,smax", [
    (2, 4, 4, 32, 128),
    (3, 8, 2, 64, 256),
    (1, 4, 1, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_shapes(b, h, hkv, hd, smax, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), dtype)
    lens = jnp.asarray(RNG.integers(1, smax + 1, (b,)), jnp.int32)
    out = decode_attention(q, k, v, lens, block_kv=64)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@given(st.integers(min_value=1, max_value=200))
@settings(max_examples=8, deadline=None)
def test_decode_attention_ragged_lengths_property(kv_len):
    """Cache entries beyond kv_len never influence the output."""
    b, h, hd, smax = 1, 2, 32, 256
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = np.asarray(RNG.normal(0, 1, (b, h, hd, smax)), np.float32)
    v = np.asarray(RNG.normal(0, 1, (b, h, hd, smax)), np.float32)
    k2, v2 = k.copy(), v.copy()
    k2[..., kv_len:] = 999.0    # poison the dead region
    v2[..., kv_len:] = -999.0
    out1 = decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                            jnp.int32(kv_len), block_kv=64)
    out2 = decode_attention(q, jnp.asarray(k2), jnp.asarray(v2),
                            jnp.int32(kv_len), block_kv=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("h,hkv", [(8, 4), (8, 1), (4, 2), (6, 3)])
@pytest.mark.parametrize("hd", [32, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_gqa_headdim_sweep(h, hkv, hd, dtype):
    """GQA group ratios (h != hkv, incl. MQA and non-pow2 heads) across
    head dims and dtypes, with ragged per-row lengths."""
    b, smax = 2, 128
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), dtype)
    lens = jnp.asarray([31, smax], jnp.int32)
    out = decode_attention(q, k, v, lens, block_kv=64)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_kvlen_edge_cases():
    """One batch mixing the ragged-length edges: a single live entry, a
    length that is no multiple of block_kv, Smax-1 and exactly Smax."""
    b, h, hd, smax = 4, 4, 32, 256
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, hd, smax)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, hd, smax)), jnp.float32)
    lens = jnp.asarray([1, 130, smax - 1, smax], jnp.int32)
    out = decode_attention(q, k, v, lens, block_kv=128)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)
    # kv_len=1 must reproduce v[:, 0] exactly (softmax over one entry)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(v[0, :, :, 0]), atol=5e-5)


def test_decode_attention_kvlen_zero_is_zero_output():
    """kv_len=0 (a slot with an empty cache) must yield a finite all-zero
    row, not NaNs. Kernel-only: the jnp oracle softmaxes over an all-masked
    row and returns garbage for length 0, so there is nothing to diff."""
    b, h, hd, smax = 2, 4, 32, 128
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, hd, smax)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, hd, smax)), jnp.float32)
    lens = jnp.asarray([0, 64], jnp.int32)
    out = np.asarray(decode_attention(q, k, v, lens, block_kv=64))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[0], np.zeros((h, hd)), atol=0)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out[1], np.asarray(ref[1]), atol=5e-5)


@pytest.mark.parametrize("block_kv", [128, 256, 512])
def test_decode_attention_block_kv_invariance(block_kv):
    """The KV tile size is a pure scheduling knob: results must match the
    oracle bit-for-tolerance at every block_kv."""
    b, h, hkv, hd, smax = 2, 4, 2, 64, 512
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), jnp.float32)
    lens = jnp.asarray([200, 511], jnp.int32)
    out = decode_attention(q, k, v, lens, block_kv=block_kv)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_decode_attention_reads_its_layer_of_the_stack(layer):
    """The stacked cache (L, B, Hkv, hd, Smax) and a layer index, ragged
    lengths: the kernel reads that layer and no other (each layer holds
    other values), the last included, whether the index is a constant or
    traced, as the scan gives it."""
    n, b, h, hkv, hd, smax = 3, 3, 8, 2, 32, 256
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (n, b, hkv, hd, smax)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (n, b, hkv, hd, smax)), jnp.float32)
    lens = jnp.asarray([1, 130, smax], jnp.int32)
    out = decode_attention(q, k, v, lens, layer=layer, block_kv=64)
    traced = jax.jit(lambda at: decode_attention(q, k, v, lens, layer=at,
                                                 block_kv=64))(layer)
    alone = decode_attention(q, k[layer], v[layer], lens, block_kv=64)
    ref = decode_attention_ref(q, k, v, lens, layer=layer)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(out))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)
    for other in set(range(n)) - {layer}:
        assert not np.allclose(np.asarray(out), np.asarray(
            decode_attention_ref(q, k, v, lens, layer=other)), atol=1e-2)


@pytest.mark.parametrize("kv_len,blocks", [
    (0, [0, 0, 0, 0]),        # an empty row reads block 0 once
    (1, [0, 0, 0, 0]),
    (64, [0, 0, 0, 0]),       # exactly one block
    (65, [0, 1, 1, 1]),       # one position into the second
    (130, [0, 1, 2, 2]),
    (256, [0, 1, 2, 3]),      # the whole cache
])
def test_decode_attention_kv_index_map_stays_on_the_last_needed_block(
        kv_len, blocks):
    """The k/v index map at each step of a row's KV axis: the step's own
    block up to the row's last needed one, that block past it, so the
    pipeline fetches max(ceil(kv_len / bkv), 1) distinct blocks, the
    count ``attn.kv_blocks_read`` takes. Layer and heads pass through."""
    at = jnp.asarray([2], jnp.int32)
    lens = jnp.asarray([256, kv_len], jnp.int32)
    maps = [tuple(int(i) for i in DK.kv_block_map(1, ik, at, lens, bkv=64))
            for ik in range(4)]
    assert maps == [(2, 1, 0, 0, blk) for blk in blocks]
    assert len(set(blocks)) == max(-(-kv_len // 64), 1)


@pytest.mark.parametrize("hkv,hd,smax,block_kv,want", [
    (16, 64, 2048, 512, 512),     # granite-3-2b: a 4 MiB pair
    (8, 128, 2048, 512, 512),     # granite-4.0-h's attention layers
    (2, 64, 2048, 512, 512),      # qwen2-0.5b
    (2, 64, 128, 512, 128),       # a cache shorter than the block
    (32, 128, 2048, 512, 256),    # an 8 MiB pair at 256
    (64, 128, 2048, 512, 128),    # never under 128 positions
    (4, 32, 192, 128, 64),        # cut to a divisor of the cache first
])
def test_decode_attention_block_size_from_the_shape(hkv, hd, smax, block_kv,
                                                    want):
    """The block holds every KV head of a row: ``block_kv`` is halved
    while a double-buffered bfloat16 K and V pair passes 8 MiB."""
    assert DK.kv_block(hkv, hd, smax, block_kv, 2) == want


@pytest.mark.parametrize("hkv", [1, 2, 8, 16])
@pytest.mark.parametrize("g", [1, 2, 7])
def test_decode_attention_folded_heads_ragged_lengths(hkv, g):
    """Every KV head of a row in one block, G query heads to each, over
    ragged lengths that end inside a block, on a block edge and in the
    first block."""
    b, hd, smax = 3, 32, 256
    q = jnp.asarray(RNG.normal(0, 1, (b, hkv * g, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, hd, smax)), jnp.float32)
    lens = jnp.asarray([200, 64, 5], jnp.int32)
    out = decode_attention(q, k, v, lens, block_kv=64)
    ref = decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_decode_attention_one_long_row_beside_short_ones():
    """The served case: one row far longer than the others. Positions past
    each row's length hold poison, so a block fetched for the wrong row
    or unmasked shows; each row matches the oracle."""
    b, h, hkv, hd, smax = 4, 8, 4, 32, 1024
    q = jnp.asarray(RNG.normal(0, 1, (b, h, hd)), jnp.float32)
    k = RNG.normal(0, 1, (b, hkv, hd, smax)).astype(np.float32)
    v = RNG.normal(0, 1, (b, hkv, hd, smax)).astype(np.float32)
    lens = np.asarray([1000, 3, 1, 130])
    for row, n in enumerate(lens):
        k[row, ..., n:] = 999.0
        v[row, ..., n:] = -999.0
    out = decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(lens, jnp.int32), block_kv=128)
    ref = decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v),
                               jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)
    assert np.abs(np.asarray(out)).max() < 10.0


# ---------------------------------------------------------------------------
# rglru scan sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d,bt,bd", [
    (1, 64, 32, 16, 32),
    (2, 128, 96, 32, 32),
    (1, 96, 48, 32, 16),    # non-pow2 sizes
])
def test_rglru_scan_shapes(b, s, d, bt, bd):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (b, s, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 0.1, (b, s, d)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 1, (b, d)), jnp.float32)
    hs, hl = rglru_scan(a, x, h0, block_t=bt, block_d=bd)
    hs_r, hl_r = rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hl_r), atol=1e-5)


def test_rglru_scan_gradients():
    b, s, d = 1, 64, 32
    a = jnp.asarray(RNG.uniform(0.7, 0.99, (b, s, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 0.1, (b, s, d)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 1, (b, d)), jnp.float32)

    def lk(a, x, h0):
        hs, hl = rglru_scan(a, x, h0, block_t=16, block_d=16)
        return jnp.sum(hs ** 2) + jnp.sum(hl)

    def lr(a, x, h0):
        hs, hl = rglru_scan_ref(a, x, h0)
        return jnp.sum(hs ** 2) + jnp.sum(hl)

    gk = jax.grad(lk, argnums=(0, 1, 2))(a, x, h0)
    gr = jax.grad(lr, argnums=(0, 1, 2))(a, x, h0)
    for g1, g2 in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=4, max_value=32))
@settings(max_examples=8, deadline=None)
def test_rglru_block_size_invariance_property(nblocks, bt):
    """The blocked scan result is independent of the block size."""
    b, d = 1, 16
    s = nblocks * bt
    a = jnp.asarray(RNG.uniform(0.5, 0.999, (b, s, d)), jnp.float32)
    x = jnp.asarray(RNG.normal(0, 0.2, (b, s, d)), jnp.float32)
    h0 = jnp.zeros((b, d), jnp.float32)
    hs1, _ = rglru_scan(a, x, h0, block_t=bt, block_d=d)
    hs2, _ = rglru_scan(a, x, h0, block_t=s, block_d=d)
    np.testing.assert_allclose(np.asarray(hs1), np.asarray(hs2), atol=1e-5)


# ---------------------------------------------------------------------------
# mlstm chunk sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,hd,chunk", [
    (1, 2, 64, 32, 16),
    (2, 3, 64, 32, 32),
    (1, 1, 128, 64, 64),
])
def test_mlstm_chunk_shapes(b, h, s, hd, chunk):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    li = jnp.asarray(RNG.normal(0, 1, (b, h, s)), jnp.float32)
    lf = jnp.asarray(-np.abs(RNG.normal(1, 0.5, (b, h, s))), jnp.float32)
    C0 = jnp.zeros((b, h, hd, hd))
    n0 = jnp.zeros((b, h, hd))
    m0 = jnp.full((b, h), -1e30)
    hs, (C, n, m) = mlstm_chunk(q, k, v, li, lf, C0, n0, m0, chunk=chunk)
    hs_r, (Cr, nr, mr) = mlstm_ref(q, k, v, li, lf, C0, n0, m0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(Cr), atol=1e-3)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr), atol=1e-5)


def test_mlstm_carried_state_continuation():
    """Processing [first half -> state -> second half] equals processing
    the full sequence at once."""
    b, h, s, hd = 1, 2, 64, 32
    q = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, h, s, hd)), jnp.float32)
    li = jnp.asarray(RNG.normal(0, 1, (b, h, s)), jnp.float32)
    lf = jnp.asarray(-np.abs(RNG.normal(1, 0.5, (b, h, s))), jnp.float32)
    zeroC = jnp.zeros((b, h, hd, hd))
    zeron = jnp.zeros((b, h, hd))
    zerom = jnp.full((b, h), -1e30)
    full, _ = mlstm_chunk(q, k, v, li, lf, zeroC, zeron, zerom, chunk=16)
    h1, (C, n, m) = mlstm_chunk(q[:, :, :32], k[:, :, :32], v[:, :, :32],
                                li[:, :, :32], lf[:, :, :32],
                                zeroC, zeron, zerom, chunk=16)
    h2, _ = mlstm_chunk(q[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                        li[:, :, 32:], lf[:, :, 32:], C, n, m, chunk=16)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(full[:, :, :32]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, :, 32:]),
                               atol=1e-4)
