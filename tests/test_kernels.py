"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret=True mode on CPU (the kernel body executes in
Python), which checks indexing, masking, and accumulation logic exactly as
it would run on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunked_prefill import (
    chunked_prefill_attention,
    chunked_prefill_paged,
)
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_chunk_scan

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,skv,h,hkv,d,off,win",
    [
        (2, 64, 64, 4, 2, 32, 0, None),        # GQA, square
        (1, 128, 256, 8, 8, 64, 128, None),    # prefix offset (chunked)
        (2, 32, 96, 4, 1, 16, 64, 48),         # MQA + sliding window
        (1, 200, 200, 2, 2, 24, 0, None),      # ragged (padding path)
        (1, 16, 144, 4, 4, 128, 128, 64),      # window + offset
    ],
)
def test_chunked_prefill_matches_oracle(b, sq, skv, h, hkv, d, off, win, dtype):
    q = _rand((b, sq, h, d), dtype)
    k = _rand((b, skv, hkv, d), dtype)
    v = _rand((b, skv, hkv, d), dtype)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=off,
                             sliding_window=win)
    got = chunked_prefill_attention(q, k, v, causal=True, q_offset=off,
                                    sliding_window=win, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_chunked_prefill_noncausal():
    q = _rand((1, 32, 2, 16), jnp.float32)
    k = _rand((1, 48, 2, 16), jnp.float32)
    v = _rand((1, 48, 2, 16), jnp.float32)
    want = ref.attention_ref(q, k, v, causal=False)
    got = chunked_prefill_attention(q, k, v, causal=False, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# paged chunked prefill (prefill chunks reading a shared page pool)
# ---------------------------------------------------------------------------

def _paged_setup(b, sq, h, hkv, d, page, p_max, n_pages, dtype=jnp.float32):
    q = _rand((b, sq, h, d), dtype)
    kp = _rand((n_pages, page, hkv, d), dtype)
    vp = _rand((n_pages, page, hkv, d), dtype)
    bt = jnp.asarray(
        RNG.permutation(n_pages)[: b * p_max].reshape(b, p_max), jnp.int32)
    return q, kp, vp, bt


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,h,hkv,d,page,p_max,offs,lens",
    [
        # ragged, non-page-multiple offsets (chunk boundaries mid-page)
        (2, 24, 4, 2, 16, 8, 4, [5, 17], [22, 31]),
        # page-aligned chunk boundaries (the scheduler's normal case)
        (2, 16, 4, 4, 32, 16, 4, [16, 32], [32, 48]),
        # zero-length suffix: all keys masked -> zeros; plus a full row
        (2, 8, 2, 1, 16, 8, 3, [0, 3], [0, 11]),
        # single-token replay chunk one position before a page boundary
        (1, 1, 4, 2, 64, 16, 4, [31], [32]),
    ],
)
def test_chunked_prefill_paged_matches_oracle(b, sq, h, hkv, d, page, p_max,
                                              offs, lens, dtype):
    q, kp, vp, bt = _paged_setup(b, sq, h, hkv, d, page, p_max,
                                 b * p_max + 2, dtype)
    offs = jnp.asarray(offs, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = ref.chunked_prefill_paged_ref(q, kp, vp, lens, bt, offs)
    got = chunked_prefill_paged(q, kp, vp, lens, bt, offs, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_chunked_prefill_paged_matches_dense_gather():
    """Reading the prefix in place through the block table == gathering
    the pages into a contiguous sequence and running the dense oracle."""
    b, sq, h, hkv, d, page, p_max = 1, 24, 4, 2, 16, 8, 4
    q, kp, vp, bt = _paged_setup(b, sq, h, hkv, d, page, p_max, 8)
    off, kv_len = 5, 5 + sq
    got = chunked_prefill_paged(
        q, kp, vp, jnp.asarray([kv_len], jnp.int32), bt,
        jnp.asarray([off], jnp.int32), interpret=True)
    k_seq = jnp.take(kp, bt[0], axis=0).reshape(1, p_max * page, hkv, d)
    v_seq = jnp.take(vp, bt[0], axis=0).reshape(1, p_max * page, hkv, d)
    want = ref.attention_ref(q, k_seq[:, :kv_len], v_seq[:, :kv_len],
                             causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_chunked_prefill_paged_zero_length_rows_are_zero():
    """A row with no visible key (lengths == 0, or the padded tail of a
    ragged final chunk) must return exactly zero in kernel and oracle."""
    b, sq, h, hkv, d, page, p_max = 2, 8, 2, 2, 8, 4, 2
    q, kp, vp, bt = _paged_setup(b, sq, h, hkv, d, page, p_max, 6)
    lens = jnp.asarray([0, 5], jnp.int32)
    offs = jnp.asarray([0, 0], jnp.int32)
    got = chunked_prefill_paged(q, kp, vp, lens, bt, offs, interpret=True)
    want = ref.chunked_prefill_paged_ref(q, kp, vp, lens, bt, offs)
    assert np.abs(np.asarray(got[0])).max() == 0.0
    assert np.abs(np.asarray(want[0])).max() == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_chunked_prefill_paged_gqa_head_mapping():
    """Query head h must read KV head h // (H/Hkv) through the table."""
    b, sq, h, hkv, d, page, p_max = 1, 8, 8, 4, 4, 8, 2
    q, kp, _, bt = _paged_setup(b, sq, h, hkv, d, page, p_max, 4)
    vp = jnp.broadcast_to(
        jnp.arange(hkv, dtype=jnp.float32)[None, None, :, None],
        kp.shape)
    lens = jnp.asarray([11], jnp.int32)
    offs = jnp.asarray([4], jnp.int32)
    out = np.asarray(chunked_prefill_paged(q, kp, vp, lens, bt, offs,
                                           interpret=True))
    rep = h // hkv
    for ih in range(h):
        np.testing.assert_allclose(out[0, :, ih], ih // rep, atol=1e-5)
    np.testing.assert_allclose(
        out, np.asarray(ref.chunked_prefill_paged_ref(q, kp, vp, lens, bt,
                                                      offs)),
        atol=2e-5, rtol=2e-4)


def test_ops_chunked_prefill_paged_dispatch():
    b, sq, h, hkv, d, page, p_max = 2, 16, 4, 2, 8, 8, 3
    q, kp, vp, bt = _paged_setup(b, sq, h, hkv, d, page, p_max, 8)
    lens = jnp.asarray([10, 20], jnp.int32)
    offs = jnp.asarray([0, 7], jnp.int32)
    a = ops.chunked_prefill_paged(q, kp, vp, lens, bt, offs, impl="jnp")
    b_ = ops.chunked_prefill_paged(q, kp, vp, lens, bt, offs, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               atol=2e-5, rtol=2e-4)


def _free_list_pool(b, h, hkv, d, page, p_max, lens, layers=3):
    """A stacked pool [layers, N, page, Hkv, D] and a free-list table:
    each row holds scattered pages for its length, and the scratch page 0
    past them (a row of length 0 is all scratch)."""
    n_pages = 1 + sum(-(-n // page) for n in lens) + 2
    kp = _rand((layers, n_pages, page, hkv, d), jnp.float32)
    vp = _rand((layers, n_pages, page, hkv, d), jnp.float32)
    free = list(RNG.permutation(np.arange(1, n_pages)))
    bt = np.zeros((b, p_max), np.int32)
    for i, n in enumerate(lens):
        for j in range(-(-n // page)):
            bt[i, j] = free.pop()
    return kp, vp, jnp.asarray(bt)


# (b, sq, h, hkv, d, page, p_max, offs, lens); decode is sq 1 at len - 1.
# D 64 on 128-token pages is the page-minor read, D 128 the token-major
# one (``page_minor``).
STACKED_CASES = {
    "decode-rep2-d128": (4, 1, 4, 2, 128, 128, 3, None,
                         [0, 200, 256, 384]),
    "decode-rep8-d64": (4, 1, 16, 2, 64, 128, 3, None, [130, 0, 128, 1]),
    "chunk-rep2-d64-mid-page": (2, 24, 4, 2, 64, 128, 2, [120, 0],
                                [144, 0]),
    "chunk-rep8-d128-to-boundary": (2, 16, 16, 2, 128, 128, 2, [240, 5],
                                    [256, 21]),
}


@pytest.mark.parametrize("layer", [None, 1, 2],
                         ids=["one-layer", "middle-layer", "last-layer"])
@pytest.mark.parametrize("case", list(STACKED_CASES))
def test_chunked_prefill_paged_reads_stacked_pool(case, layer):
    """The stacked pool read in place at a layer == the oracle over that
    layer's pool, through a free-list table with scattered and scratch
    pages; decode goes through its own entry point."""
    b, sq, h, hkv, d, page, p_max, offs, lens = STACKED_CASES[case]
    kp, vp, bt = _free_list_pool(b, h, hkv, d, page, p_max, lens)
    lens = jnp.asarray(lens, jnp.int32)
    k_l, v_l = kp[layer or 1], vp[layer or 1]     # the layer read
    k_in, v_in = (k_l, v_l) if layer is None else (kp, vp)
    if sq == 1:
        q = _rand((b, h, d), jnp.float32)
        got = paged_attention(q, k_in, v_in, lens, block_tables=bt,
                              layer=layer, interpret=True)
        want = ref.paged_attention_ref(q, k_l, v_l, lens, block_tables=bt)
    else:
        q = _rand((b, sq, h, d), jnp.float32)
        offs = jnp.asarray(offs, jnp.int32)
        got = chunked_prefill_paged(q, k_in, v_in, lens, bt, offs,
                                    layer=layer, interpret=True)
        want = ref.chunked_prefill_paged_ref(q, k_l, v_l, lens, bt, offs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)
    assert np.abs(np.asarray(got)[np.asarray(lens) == 0]).max(
        initial=0.0) == 0.0


def test_page_minor_follows_the_lane_dim():
    from repro.kernels.chunked_prefill import page_minor

    assert page_minor(128, 64) and page_minor(128, 96)
    assert not page_minor(128, 128) and not page_minor(128, 256)
    assert not page_minor(16, 64)


# ---------------------------------------------------------------------------
# paged attention (decode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,p,page,h,hkv,d",
    [
        (2, 4, 32, 4, 2, 16),
        (1, 8, 16, 8, 1, 64),
        (3, 2, 128, 4, 4, 32),
        (2, 16, 8, 2, 2, 128),
    ],
)
def test_paged_attention_matches_oracle(b, p, page, h, hkv, d, dtype):
    q = _rand((b, h, d), dtype)
    k = _rand((b, p, page, hkv, d), dtype)
    v = _rand((b, p, page, hkv, d), dtype)
    lengths = jnp.asarray(RNG.integers(1, p * page + 1, size=(b,)), jnp.int32)
    want = ref.paged_attention_ref(q, k, v, lengths)
    got = paged_attention(q, k, v, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_paged_attention_length_edge_cases():
    """Ragged lengths: empty (0), single token, partial final page, page
    boundary, boundary+1, completely full."""
    b, p, page, h, d = 2, 3, 16, 2, 8
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, p, page, h, d), jnp.float32)
    v = _rand((b, p, page, h, d), jnp.float32)
    for lengths in ([0, 48], [1, 41], [16, 17], [0, 0], [15, 33], [48, 48]):
        lg = jnp.asarray(lengths, jnp.int32)
        want = ref.paged_attention_ref(q, k, v, lg)
        got = paged_attention(q, k, v, lg, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-4, err_msg=str(lengths))


def test_paged_attention_zero_length_returns_zeros():
    """A slot with no cached tokens (freshly admitted / idle) must produce
    exactly zero, not a uniform average over garbage pages."""
    b, p, page, h, d = 1, 2, 8, 2, 4
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, p, page, h, d), jnp.float32)
    v = _rand((b, p, page, h, d), jnp.float32)
    lg = jnp.asarray([0], jnp.int32)
    assert np.abs(np.asarray(
        paged_attention(q, k, v, lg, interpret=True))).max() == 0.0
    assert np.abs(np.asarray(ref.paged_attention_ref(q, k, v, lg))).max() == 0.0


def test_paged_attention_gqa_head_mapping():
    """Query head h must read KV head h // (H/Hkv).  Values are constant
    per KV head, so any mapping mistake shifts the output by >= 1."""
    b, p, page, h, hkv, d = 1, 2, 8, 8, 4, 4
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, p, page, hkv, d), jnp.float32)
    v = jnp.broadcast_to(
        jnp.arange(hkv, dtype=jnp.float32)[None, None, None, :, None],
        (b, p, page, hkv, d),
    )
    lengths = jnp.asarray([11], jnp.int32)
    out = np.asarray(paged_attention(q, k, v, lengths, interpret=True))
    rep = h // hkv
    for ih in range(h):
        np.testing.assert_allclose(out[0, ih], ih // rep, atol=1e-5)
    np.testing.assert_allclose(
        out, np.asarray(ref.paged_attention_ref(q, k, v, lengths)),
        atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_block_tables(dtype):
    """Shared-pool layout: kernel with scalar-prefetched block tables ==
    oracle == gathering pages into contiguous order first."""
    b, h, hkv, d, page, p_max, n_pages = 3, 4, 2, 16, 8, 4, 16
    q = _rand((b, h, d), dtype)
    kp = _rand((n_pages, page, hkv, d), dtype)
    vp = _rand((n_pages, page, hkv, d), dtype)
    bt = jnp.asarray(
        RNG.permutation(n_pages)[: b * p_max].reshape(b, p_max), jnp.int32)
    lengths = jnp.asarray([0, 13, 32], jnp.int32)
    want = ref.paged_attention_ref(q, kp, vp, lengths, block_tables=bt)
    got = paged_attention(q, kp, vp, lengths, block_tables=bt,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # contiguous gather of the same tables gives the same attention
    contig = paged_attention(q, kp[bt], vp[bt], lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(contig, np.float32), **_tol(dtype))


def test_paged_attention_block_tables_share_prefix_pages():
    """Two sequences may alias the same physical pages (a shared SkyMemory
    prefix): results must equal private copies of those pages."""
    b, h, hkv, d, page = 2, 2, 2, 8, 4
    q = _rand((b, h, d), jnp.float32)
    pool = _rand((6, page, hkv, d), jnp.float32)
    bt_shared = jnp.asarray([[1, 2], [1, 3]], jnp.int32)   # page 1 shared
    bt_private = jnp.asarray([[4, 2], [5, 3]], jnp.int32)
    pool_priv = pool.at[4].set(pool[1]).at[5].set(pool[1])
    lengths = jnp.asarray([7, 5], jnp.int32)
    a = paged_attention(q, pool, pool, lengths, block_tables=bt_shared,
                        interpret=True)
    c = paged_attention(q, pool_priv, pool_priv, lengths,
                        block_tables=bt_private, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,l,h,p,g,n,q",
    [
        (2, 128, 4, 8, 2, 16, 32),
        (1, 64, 8, 16, 1, 32, 64),
        (2, 256, 2, 32, 2, 8, 128),
        (1, 96, 4, 64, 4, 128, 32),   # full mamba2-like head/state dims
    ],
)
def test_ssd_scan_matches_oracle(b, l, h, p, g, n, q, dtype):
    x = _rand((b, l, h, p), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm = _rand((b, l, g, n), dtype)
    cm = _rand((b, l, g, n), dtype)
    init = jnp.asarray(RNG.standard_normal((b, h, p, n)), jnp.float32)
    yw, fw = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk_size=q,
                              initial_state=init)
    yg, fg = ssd_chunk_scan(x, dt, a, bm, cm, chunk_size=q,
                            initial_state=init, interpret=True)
    np.testing.assert_allclose(
        np.asarray(yg, np.float32), np.asarray(yw, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(fg), np.asarray(fw), atol=1e-4,
                               rtol=1e-3)


def test_ssd_scan_equals_sequential_recurrence():
    """Chunked kernel == token-by-token decode recurrence (ground truth)."""
    b, l, h, p, g, n = 1, 64, 2, 4, 1, 8
    x = _rand((b, l, h, p), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    bm = _rand((b, l, g, n), jnp.float32)
    cm = _rand((b, l, g, n), jnp.float32)
    y, fs = ssd_chunk_scan(x, dt, a, bm, cm, chunk_size=16, interpret=True)
    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(l):
        yt, state = ref.ssd_decode_step_ref(
            x[:, t], dt[:, t], a, bm[:, t], cm[:, t], state
        )
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(ys, 1)),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(state), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# ops dispatcher
# ---------------------------------------------------------------------------

def test_ops_dispatch_jnp_vs_pallas(monkeypatch):
    q = _rand((1, 32, 2, 16), jnp.float32)
    k = _rand((1, 32, 2, 16), jnp.float32)
    v = _rand((1, 32, 2, 16), jnp.float32)
    a = ops.flash_attention(q, k, v, impl="jnp")
    b = ops.flash_attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-4)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jnp")
    c = ops.flash_attention(q, k, v, impl="pallas")  # env overrides
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=0)


def test_ops_paged_dispatch_block_tables():
    """ops.paged_attention routes block tables to both implementations."""
    b, h, hkv, d, page, p_max, n_pages = 2, 4, 2, 8, 4, 3, 8
    q = _rand((b, h, d), jnp.float32)
    kp = _rand((n_pages, page, hkv, d), jnp.float32)
    vp = _rand((n_pages, page, hkv, d), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([5, 12], jnp.int32)
    a = ops.paged_attention(q, kp, vp, lengths, block_tables=bt, impl="jnp")
    b_ = ops.paged_attention(q, kp, vp, lengths, block_tables=bt,
                             impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               atol=2e-5, rtol=2e-4)


def test_ops_paged_dispatch_stacked_pool():
    """ops routes a stacked pool and its layer to both implementations."""
    h, hkv, d, page = 4, 2, 8, 4
    lens = [5, 12]
    kp, vp, bt = _free_list_pool(2, h, hkv, d, page, 3, lens)
    q = _rand((2, h, d), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    qc = _rand((2, 3, h, d), jnp.float32)
    offs = lens - 3
    for layer in (0, 2):
        a = ops.paged_attention(q, kp, vp, lens, block_tables=bt,
                                layer=layer, impl="jnp")
        b_ = ops.paged_attention(q, kp, vp, lens, block_tables=bt,
                                 layer=layer, impl="pallas")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-4)
        a = ops.chunked_prefill_paged(qc, kp, vp, lens, bt, offs,
                                      layer=layer, impl="jnp")
        b_ = ops.chunked_prefill_paged(qc, kp, vp, lens, bt, offs,
                                       layer=layer, impl="pallas")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-4)


def test_paged_attention_grouped_matches_repeat():
    """Grouped-GQA decode (no head-repeat materialization) == baseline."""
    b, p, page, h, hkv, d = 2, 4, 32, 8, 2, 16
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, p, page, hkv, d), jnp.float32)
    v = _rand((b, p, page, hkv, d), jnp.float32)
    lengths = jnp.asarray([50, 120], jnp.int32)
    base = ref.paged_attention_ref(q, k, v, lengths, grouped=False)
    grp = ref.paged_attention_ref(q, k, v, lengths, grouped=True)
    np.testing.assert_allclose(np.asarray(grp), np.asarray(base),
                               atol=2e-5, rtol=2e-4)
