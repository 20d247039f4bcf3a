"""GQA attention: prefill (flash/chunked) + decode (paged KV cache).

The decode path consumes the block-paged KV cache -- the tensor SkyMemory
blocks, chunks and stripes.  Sliding-window decode uses the same cache as a
ring buffer (the ``long_500k`` variant for full-attention architectures).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import maybe_shard
from repro.kernels import ops
from repro.models.cache import KVC_INT8_SCALE, dequant_kvc, quant_kvc
from repro.models.config import ModelConfig
from repro.models.layers import dense_init
from repro.models.rope import apply_rope

PAGE_SIZE = 128  # KV-cache page (= the paper's 128-token block)

_quant = quant_kvc
_dequant = dequant_kvc


def init_attention(key, cfg: ModelConfig):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dt),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=dt),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=dt),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dt),
    }


def _project_qkv(params, x, kv_x, cfg: ModelConfig):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    skv = kv_x.shape[1]
    k = (kv_x @ params["wk"]).reshape(b, skv, hkv, hd)
    v = (kv_x @ params["wv"]).reshape(b, skv, hkv, hd)
    return q, k, v


def attention_prefill(
    params,
    x,
    cfg: ModelConfig,
    *,
    q_offset=0,
    sliding_window: int | None = None,
    kv_x=None,
    causal: bool = True,
    kv_cache: tuple[jax.Array, jax.Array] | None = None,
):
    """Full-sequence attention.  ``kv_cache=(k_prefix, v_prefix)`` implements
    chunked prefill on top of a SkyMemory-restored prefix: fresh K/V are
    appended after the cached prefix and queries attend across both."""
    b, s, _ = x.shape
    cross = kv_x is not None
    kv_src = kv_x if cross else x
    q, k, v = _project_qkv(params, x, kv_src, cfg)
    if not cross:
        q_pos = jnp.arange(s) + q_offset
        k_pos = jnp.arange(k.shape[1]) + q_offset
        q = apply_rope(q, q_pos, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, k_pos, cfg.rope_theta, cfg.rotary_pct)
    if kv_cache is not None:
        k = jnp.concatenate([kv_cache[0].astype(k.dtype), k], axis=1)
        v = jnp.concatenate([kv_cache[1].astype(v.dtype), v], axis=1)
    out = ops.flash_attention(
        q, k, v,
        causal=causal and not cross,
        q_offset=(kv_cache[0].shape[1] if kv_cache is not None else 0)
        if not cross else 0,
        sliding_window=sliding_window,
    )
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"], (k, v)


def attention_prefill_paged(
    params,
    x,                     # [R, C, d_model] one prefill chunk per row
    cfg: ModelConfig,
    *,
    k_pool,                # [L, N_pages, page, Hkv, hd] stacked page pool
    v_pool,
    layer,                 # this layer's index into the pools
    block_tables,          # [R, P] page ids of each row's slot
    q_offsets,             # [R] int32: chunk starts (absolute positions)
    n_valid,               # [R] int32: valid tokens per chunk (<= C)
):
    """A batch of prefill chunks against the shared page pool; returns
    ``(out, k_pool', v_pool')``.

    Each row's chunk K/V are written into its slot's pages of ``layer``
    *first* (a chunk start need not be page-aligned -- the
    whole-prompt-cached replay starts one token before a block boundary),
    then the chunk's queries attend over everything valid so far:
    SkyMemory-restored pages, earlier chunks, and this chunk, all read in
    place through the block tables.  Positions past ``n_valid`` (the
    padded tail of a ragged final chunk, or an all-padding batch row) are
    left out of the write and their outputs are garbage the scheduler
    never reads.  ``q_offsets`` / ``n_valid`` are traced values: one
    compilation per chunk-buffer shape serves every chunk of every
    admission.
    """
    r, c = x.shape[0], x.shape[1]
    h, hd = cfg.num_heads, cfg.head_dim
    q_offsets = jnp.asarray(q_offsets, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)

    q, k_new, v_new = _project_qkv(params, x, x, cfg)
    positions = q_offsets[:, None] + jnp.arange(c, dtype=jnp.int32)  # [R, C]
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.rotary_pct)
    q = maybe_shard(q, "decode_qkv")
    k_new = maybe_shard(k_new, "decode_qkv")
    v_new = maybe_shard(v_new, "decode_qkv")

    int8_kvc = k_pool.dtype == jnp.int8
    if int8_kvc:
        k_new, v_new = _quant(k_new), _quant(v_new)
    k_pool = _write_chunks(k_pool, k_new, layer, block_tables, q_offsets,
                           n_valid)
    v_pool = _write_chunks(v_pool, v_new, layer, block_tables, q_offsets,
                           n_valid)
    k_read, v_read, read_layer = _readable(k_pool, v_pool, layer, x.dtype)
    out = ops.chunked_prefill_paged(
        q, k_read, v_read, q_offsets + n_valid, block_tables, q_offsets,
        layer=read_layer,
    )
    return out.reshape(r, c, h * hd) @ params["wo"], k_pool, v_pool


def attention_decode(
    params,
    x,                     # [B, 1, d_model]
    cfg: ModelConfig,
    *,
    k_cache,               # [B, S_cache, Hkv, hd]
    v_cache,
    pos,                   # scalar int32: number of tokens already cached
    sliding_window: int | None = None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
):
    """One-token decode over the paged cache; returns (out, k', v').

    With ``sliding_window`` the cache is a ring buffer of ``window`` slots
    (sub-quadratic memory for long_500k); RoPE is applied at the *absolute*
    position before writing, so relative phases stay correct after wrap.
    """
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    if cross_kv is not None:
        q = (x @ params["wq"]).reshape(b, 1, h, hd)[:, 0]
        k, v = cross_kv
        lengths = jnp.full((b,), k.shape[1], jnp.int32)
        out = _paged(q, k, v, lengths)
        return out.reshape(b, 1, h * hd) @ params["wo"], k_cache, v_cache

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))  # per-sequence
    q, k_new, v_new = _project_qkv(params, x, x, cfg)
    positions = pos[:, None]                              # [B,1] abs position
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.rotary_pct)
    # with TP attention projections + a model-striped cache, gather the tiny
    # q/k/v here rather than letting SPMD gather the cache
    q = maybe_shard(q, "decode_qkv")
    k_new = maybe_shard(k_new, "decode_qkv")
    v_new = maybe_shard(v_new, "decode_qkv")

    s_cache = k_cache.shape[1]
    slot = pos % s_cache if sliding_window else pos
    # Masked one-hot write: elementwise on the (possibly sequence-sharded)
    # cache, so SPMD keeps every shard local -- a scatter/DUS on a sharded
    # seq dim would force a full cache all-gather.
    onehot = (jnp.arange(s_cache, dtype=jnp.int32)[None, :]
              == slot[:, None])[..., None, None]          # [B,S,1,1]
    int8_kvc = k_cache.dtype == jnp.int8
    if int8_kvc:  # quantized KVC (paper's 8-bit memory trade-off)
        k_new, v_new = _quant(k_new), _quant(v_new)
    k_cache = jnp.where(onehot, k_new.astype(k_cache.dtype), k_cache)
    v_cache = jnp.where(onehot, v_new.astype(v_cache.dtype), v_cache)
    n_valid = jnp.minimum(pos + 1, s_cache) if sliding_window else pos + 1
    if int8_kvc:
        k_read = _dequant(k_cache, x.dtype)
        v_read = _dequant(v_cache, x.dtype)
    else:
        k_read, v_read = k_cache, v_cache
    out = _paged(q[:, 0], k_read, v_read, n_valid.astype(jnp.int32))
    return out.reshape(b, 1, h * hd) @ params["wo"], k_cache, v_cache


def attention_decode_paged(
    params,
    x,                     # [B, 1, d_model]
    cfg: ModelConfig,
    *,
    k_pool,                # [L, N_pages, page, Hkv, hd] stacked page pool
    v_pool,
    layer,                 # this layer's index into the pools
    block_tables,          # [B, P] page ids per slot; None in contiguous mode
    lengths,               # [B] int32: tokens already cached per sequence
    contiguous: bool = False,
):
    """One-token decode against the shared page pool (continuous batching).

    Per-sequence positions are heterogeneous (slots admit mid-decode), so
    RoPE, the page write, and the attention mask are all driven by
    ``lengths``.  The new K/V is written into the page of ``layer``
    holding position ``lengths[b]`` -- pages are exclusive to a slot, so
    the rows' writes never collide (idle slots write into their own
    region / the scratch page, which the next admission overwrites).

    ``contiguous`` (slot-region pools): slot ``b`` owns pages
    ``[b*P, (b+1)*P)``, so the block table is arithmetic, made inside
    the program (none is uploaded).  Either way attention reads the pool
    in place through the table (the scalar-prefetch kernel path).
    """
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    page = k_pool.shape[2]
    pos = jnp.asarray(lengths, jnp.int32)                  # [B]

    q, k_new, v_new = _project_qkv(params, x, x, cfg)
    positions = pos[:, None]                               # [B,1] abs position
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.rotary_pct)
    q = maybe_shard(q, "decode_qkv")
    k_new = maybe_shard(k_new, "decode_qkv")
    v_new = maybe_shard(v_new, "decode_qkv")

    if contiguous:
        p_max = k_pool.shape[1] // b
        block_tables = jnp.arange(b * p_max, dtype=jnp.int32).reshape(
            b, p_max)
    page_ids = jnp.take_along_axis(
        block_tables, (pos // page)[:, None], axis=1)[:, 0]  # [B]
    slots = pos % page
    int8_kvc = k_pool.dtype == jnp.int8
    if int8_kvc:
        k_new, v_new = _quant(k_new), _quant(v_new)
    k_pool = _write_tokens(k_pool, k_new[:, 0], layer, page_ids, slots)
    v_pool = _write_tokens(v_pool, v_new[:, 0], layer, page_ids, slots)
    k_read, v_read, read_layer = _readable(k_pool, v_pool, layer, x.dtype)
    out = ops.paged_attention(
        q[:, 0], k_read, v_read, pos + 1, block_tables=block_tables,
        layer=read_layer,
    )
    return out.reshape(b, 1, h * hd) @ params["wo"], k_pool, v_pool


def _readable(k_pool, v_pool, layer, dtype):
    """What attention reads: the stacked pools at ``layer``, in place; an
    int8 pool's layer dequantized to ``dtype`` (one layer's pool, read
    with ``layer=None``)."""
    if k_pool.dtype != jnp.int8:
        return k_pool, v_pool, layer
    return _dequant(k_pool[layer], dtype), _dequant(v_pool[layer], dtype), None


# Pool writes are row-by-row dynamic_update_slices into the stacked pool,
# not scatters: they update the pool in place in whatever layout the
# device keeps it, where a scatter makes XLA re-lay the pool out around it.

def _write_tokens(pool, new, layer, page_ids, slots):
    """One token per row: ``new[b]`` [Hkv, hd] at ``(layer, page_ids[b],
    slots[b])`` of ``pool`` [L, N_pages, page, Hkv, hd]."""
    for b in range(new.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[b][None, None, None].astype(pool.dtype),
            (layer, page_ids[b], slots[b], 0, 0))
    return pool


def _write_chunks(pool, new, layer, block_tables, q_offsets, n_valid):
    """Row ``r``'s first ``n_valid[r]`` tokens of ``new`` [R, C, Hkv, hd]
    at absolute positions ``q_offsets[r] + i`` of ``layer``, through its
    block-table row.  A chunk need not start on a page boundary, so each
    page it can touch is read, blended with the chunk's tokens and
    written back whole; positions outside ``[q_offsets, q_offsets +
    n_valid)`` keep what the page held (an all-padding row rewrites its
    pages unchanged).  Pages are read and written one after another, so
    a page reached twice keeps both writes; what goes into each page is
    worked out for all of them at once, which keeps the traced program
    small."""
    r, c = new.shape[:2]
    page = pool.shape[2]
    num_tables = block_tables.shape[1]
    span = (c + page - 2) // page + 1       # pages C tokens can straddle
    p_idx = q_offsets[:, None] // page + jnp.arange(span)        # [R, S]
    t = (p_idx[..., None] * page + jnp.arange(page)
         - q_offsets[:, None, None])                             # [R, S, page]
    valid = ((t >= 0) & (t < n_valid[:, None, None])
             & (p_idx < num_tables)[..., None])[..., None, None]
    pids = jnp.take_along_axis(block_tables,
                               jnp.minimum(p_idx, num_tables - 1), axis=1)
    vals = jnp.take_along_axis(
        new, jnp.clip(t, 0, c - 1).reshape(r, span * page, 1, 1), axis=1)
    vals = vals.reshape(valid.shape[:3] + new.shape[2:]).astype(pool.dtype)
    for i in range(r):
        for j in range(span):
            at = (layer, pids[i, j], 0, 0, 0)
            old = jax.lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])
            pool = jax.lax.dynamic_update_slice(
                pool, jnp.where(valid[i, j], vals[i, j], old), at)
    return pool


def _paged(q, k_cache, v_cache, lengths):
    """View the contiguous cache as pages and run the paged-decode kernel."""
    b, s, hkv, hd = k_cache.shape
    page = PAGE_SIZE if s % PAGE_SIZE == 0 else s
    kp = k_cache.reshape(b, s // page, page, hkv, hd)
    vp = v_cache.reshape(b, s // page, page, hkv, hd)
    return ops.paged_attention(q, kp, vp, lengths)
