"""Pallas TPU kernels: chunked-prefill flash attention, dense and paged.

Both compute causal attention where a block of queries starts
``q_offset`` tokens into the key sequence -- exactly the shape of a
prefill on top of a SkyMemory-restored prefix (fresh queries over
prefix + fresh keys).  Single-token decode is the one-query case of the
paged kernel (``paged_attention.py``).

Layout.  The TPU compiler accepts a block only if its last two dims are
multiples of (8, 128) or equal to the array's own.  So:

* queries are regrouped per KV head: ``[B, Hkv, nq, rep*bq, D]``, the
  ``rep = H/Hkv`` query heads of one group times ``bq`` tokens as the rows
  of one group's slab (row ``r*bq + t``), whose last two dims are whole;
* the dense kernel reads its keys and values (one forward's activations)
  transposed, ``[B, Hkv, D, tokens]``: a block is one KV head's
  ``[D, block_k]`` slab, and its grid is (batch, kv_heads, q_blocks,
  kv_blocks);
* the paged kernel reads the serving engine's stacked pool
  ``[L, N, page, Hkv, D]`` in place, in the layout the TPU stores it in:
  a block is one page of one layer with all its KV heads, and its grid
  is (batch, q_blocks, pages), each step serving every group.  Where
  head_dim is a multiple of 128 the pool is token-major and a group's
  keys ``k[:, g, :]`` are a strided read of the block's rows in VMEM;
  where it is not, the TPU keeps the page axis minor (``page_minor``),
  the kernel names the same bytes ``[L, N, Hkv, D, page]`` and a group
  is a leading index.  Either way no program lays a layer, or the pool,
  out again around the kernel.

The kv dimension is innermost in both grids, so the online-softmax
running state (m, l, acc) lives in VMEM scratch and persists across kv
iterations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
MIN_ROWS = 8        # query rows per group: one sublane tile at least


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend(q_of, k_of, v_of, mask, m_ref, l_ref, acc_ref, scale,
            kv_major: bool = False):
    """One online-softmax step for every KV group: group ``g``'s query
    rows ``q_of(g)`` [R, D] over head ``g``'s keys ``k_of(g)`` and values
    ``v_of(g)`` in the block, [K, D] and [K, Dv] -- or [D, K] and [Dv, K]
    where ``kv_major``.  Masked scores contribute exactly 0, even when the
    whole block is masked (where ``m_new == NEG_INF`` would otherwise make
    ``exp(s - m_new) == 1``)."""
    k_axis, v_axis = (0, 1) if kv_major else (1, 0)
    for g in range(m_ref.shape[0]):
        v = v_of(g)
        s = jax.lax.dot_general(
            q_of(g), k_of(g), (((1,), (k_axis,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                        # [R, K]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[g]                                # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[g] = corr * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[g] = acc_ref[g] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (v_axis,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [R, Dv]
        m_ref[g] = m_new


def _finalize(o_ref, l_ref, acc_ref):
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, :, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _positions(shape, block_q: int, q_start, k_start):
    """Absolute query / key positions of a [rows, K] score block; row
    ``r*bq + t`` is token ``t`` of its head (``bq`` is a power of two)."""
    t = jax.lax.broadcasted_iota(jnp.int32, shape, 0) & (block_q - 1)
    k = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return q_start + t, k_start + k


def _block_q(sq: int, rep: int, block_q: int) -> int:
    """Query tokens per block: a power of two covering short chunks, at
    most ``block_q``, and enough that a group has ``MIN_ROWS`` rows."""
    if block_q & (block_q - 1):
        raise ValueError(f"block_q {block_q} is not a power of two")
    bq = min(block_q, pl.next_power_of_2(sq))
    while rep * bq < MIN_ROWS:
        bq *= 2
    return bq


def _group_queries(q, hkv: int, bq: int):
    """[B, Sq, H, D] -> [B, Hkv, nq, rep*bq, D] (Sq zero-padded to nq*bq)."""
    b, sq, h, d = q.shape
    rep = h // hkv
    nq = -(-sq // bq)
    q = jnp.pad(q, ((0, 0), (0, nq * bq - sq), (0, 0), (0, 0)))
    q = q.reshape(b, nq, bq, hkv, rep, d).transpose(0, 3, 1, 4, 2, 5)
    return q.reshape(b, hkv, nq, rep * bq, d)


def _ungroup(out, sq: int, bq: int):
    """Inverse of ``_group_queries``: -> [B, Sq, H, Dv]."""
    b, hkv, nq, rows, dv = out.shape
    rep = rows // bq
    out = out.reshape(b, hkv, nq, rep, bq, dv).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(b, nq * bq, hkv * rep, dv)[:, :sq]


def _kv_major(x):
    """[..., T, Hkv, D] -> [..., Hkv, D, T]: one KV head's [D, T] slab per
    block, whole in its last two dims."""
    return jnp.moveaxis(x, -3, -1)


def _state(hkv: int, rows: int, dv: int):
    """Per-group online-softmax state in VMEM scratch."""
    return [
        pltpu.VMEM((hkv, rows, 1), jnp.float32),     # running max
        pltpu.VMEM((hkv, rows, 1), jnp.float32),     # running denom
        pltpu.VMEM((hkv, rows, dv), jnp.float32),    # output accumulator
    ]


def _dense_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, q_offset: int,
                  sliding_window: int | None, block_q: int, block_k: int,
                  kv_len: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    rows = q_ref.shape[-2]
    q_pos, k_pos = _positions((rows, block_k), block_q,
                              q_offset + iq * block_q, ik * block_k)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    _attend(lambda g: q_ref[0, 0, 0], lambda g: k_ref[0, 0],
            lambda g: v_ref[0, 0], mask, m_ref, l_ref, acc_ref, scale,
            kv_major=True)

    @pl.when(ik == num_kv_blocks - 1)
    def _():
        _finalize(o_ref, l_ref, acc_ref)


def chunked_prefill_attention(
    q, k, v, *,
    causal: bool = True,
    q_offset: int = 0,
    sliding_window: int | None = None,
    lengths=None,
    softmax_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """q: [B,Sq,H,Dq]; k/v: [B,Skv,Hkv,D].  Returns [B,Sq,H,Dv].

    ``lengths`` is not supported by this kernel (decode masking belongs to
    paged_attention); the jnp reference handles that case.
    """
    if lengths is not None:
        raise NotImplementedError("use paged_attention for length masking")
    b, sq, h, dq = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else dq ** -0.5
    rep = h // hkv

    bq = _block_q(sq, rep, block_q)
    # a key block is a multiple of 128 lanes, or the whole (short) sequence
    bk = min(block_k, pl.next_power_of_2(skv))
    nk = -(-skv // bk)
    pad = ((0, 0), (0, nk * bk - skv), (0, 0), (0, 0))
    qg = _group_queries(q, hkv, bq)
    kt = _kv_major(jnp.pad(k, pad))                     # [B, Hkv, Dq, Skv']
    vt = _kv_major(jnp.pad(v, pad))                     # [B, Hkv, Dv, Skv']
    nq, rows = qg.shape[2], qg.shape[3]

    kernel = functools.partial(
        _dense_kernel, scale=scale, causal=causal, q_offset=q_offset,
        sliding_window=sliding_window, block_q=bq, block_k=bk,
        kv_len=skv, num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, rows, dq),
                         lambda ib, ig, iq, ik: (ib, ig, iq, 0, 0)),
            pl.BlockSpec((1, 1, dq, bk),
                         lambda ib, ig, iq, ik: (ib, ig, 0, ik)),
            pl.BlockSpec((1, 1, dv, bk),
                         lambda ib, ig, iq, ik: (ib, ig, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, rows, dv),
                               lambda ib, ig, iq, ik: (ib, ig, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq, rows, dv), q.dtype),
        scratch_shapes=_state(1, rows, dv),
        name="chunked_prefill_attention",
        interpret=interpret,
    )(qg, kt, vt)
    return _ungroup(out, sq, bq)


def _last_page(lens, offs, ib, iq, *, page: int, block_q: int):
    """The last page a row's query block ``iq`` can see: the one holding
    its last key, or its block's last query if that comes first (0 for
    a row with no key)."""
    last = jnp.minimum(lens[ib], offs[ib] + (iq + 1) * block_q) - 1
    return jnp.maximum(last, 0) // page


def page_minor(page: int, head_dim: int) -> bool:
    """Whether the TPU stores a pool ``[..., page, Hkv, head_dim]`` with
    the page axis minor.  Its layout keeps a multiple of 128 in the lane
    (minor) dim where it can: a head_dim that is not one gives way to a
    page that is."""
    return head_dim % 128 != 0 and page % 128 == 0


def _paged_kernel(layer_ref, len_ref, off_ref, bt_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, page: int,
                  block_q: int, num_pages: int, kv_major: bool):
    """Paged variant: the page id for (sequence, page-slot) was resolved in
    the index map from the scalar-prefetched block table, and the causal
    offset / valid length arrive per sequence through SMEM (they are
    traced values in the serving engine's fused step, not compile-time
    constants like the dense kernel's ``q_offset``).  Pages past a row's
    last visible page are neither fetched (the index map repeats the
    last one) nor computed."""
    del layer_ref, bt_ref
    ib = pl.program_id(0)
    iq = pl.program_id(1)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    @pl.when(ip <= _last_page(len_ref, off_ref, ib, iq, page=page,
                              block_q=block_q))
    def _():
        rows = q_ref.shape[-2]
        q_pos, k_pos = _positions((rows, page), block_q,
                                  off_ref[ib] + iq * block_q, ip * page)
        mask = (k_pos <= q_pos) & (k_pos < len_ref[ib])
        if kv_major:        # [Hkv, D, page]: a head is a leading index
            k_of, v_of = (lambda g: k_ref[0, 0, g]), (lambda g: v_ref[0, 0, g])
        else:               # [page, Hkv, D]: a head is a strided read
            k_of = lambda g: k_ref[0, 0, :, g, :]          # noqa: E731
            v_of = lambda g: v_ref[0, 0, :, g, :]          # noqa: E731
        _attend(lambda g: q_ref[0, g, 0], k_of, v_of, mask,
                m_ref, l_ref, acc_ref, scale, kv_major)

    @pl.when(ip == num_pages - 1)
    def _():
        _finalize(o_ref, l_ref, acc_ref)


def chunked_prefill_paged(
    q, k_pool, v_pool, lengths, block_tables, q_offsets, *,
    layer=None,
    softmax_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    name: str = "chunked_prefill_paged",
    interpret: bool = False,
):
    """Chunked prefill reading keys straight from a shared page pool.

    q: [B,Sq,H,Dq] (one chunk per sequence); k/v pool: the stacked pool
    [L,N,page,Hkv,D] read at ``layer`` (an int or a traced scalar), or
    with ``layer=None`` one layer's pool [N,page,Hkv,D]; lengths [B] total
    valid kv tokens; block_tables [B,P] page ids; q_offsets [B] absolute
    position of each chunk's first query.  Returns [B,Sq,H,Dv].  Unlike
    ``chunked_prefill_attention`` the offset, length and layer are
    *runtime* values (scalar prefetch), so one compiled kernel serves
    every chunk of a prefill as it advances and every layer of a scan --
    and the prefix pages (SkyMemory-restored blocks, earlier chunks) are
    read in place, in the layout the device stores the pool in
    (``page_minor``), never gathered into a contiguous per-sequence
    tensor.  Fully masked query rows (padded chunk tail, ``lengths ==
    0``) return zeros.  ``name`` names the kernel's call in a device
    trace.
    """
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    b, sq, h, dq = q.shape
    _, _, page, hkv, dv = v_pool.shape
    np_ = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else dq ** -0.5
    rep = h // hkv

    bq = _block_q(sq, rep, block_q)
    qg = _group_queries(q, hkv, bq)
    nq, rows = qg.shape[2], qg.shape[3]
    last_page = functools.partial(_last_page, page=page, block_q=bq)
    kv_major = page_minor(page, dq) and page_minor(page, dv)
    if kv_major:    # the same bytes, named in their physical order
        k_pool, v_pool = jnp.moveaxis(k_pool, 2, 4), jnp.moveaxis(v_pool, 2, 4)

    def kv_map(ib, iq, ip, lay, lens, offs, bt):
        ip = jnp.minimum(ip, last_page(lens, offs, ib, iq))
        return lay[0], bt[ib, ip], 0, 0, 0

    def q_map(ib, iq, ip, *_):
        return ib, 0, iq, 0, 0

    kernel = functools.partial(
        _paged_kernel, scale=scale, page=page, block_q=bq, num_pages=np_,
        kv_major=kv_major,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nq, np_),
        in_specs=[
            pl.BlockSpec((1, hkv, 1, rows, dq), q_map),
            pl.BlockSpec((1, 1) + k_pool.shape[2:], kv_map),
            pl.BlockSpec((1, 1) + v_pool.shape[2:], kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, 1, rows, dv), q_map),
        scratch_shapes=_state(hkv, rows, dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq, rows, dv), q.dtype),
        name=name,
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      q_offsets.astype(jnp.int32), block_tables.astype(jnp.int32), qg,
      k_pool, v_pool)
    return _ungroup(out, sq, bq)
