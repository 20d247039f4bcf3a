"""Pallas TPU kernel: single-token decode attention over a paged KV cache.

The cache is the block-paged tensor SkyMemory stripes: pages of
``page_size`` tokens (the paper's 128-token blocks) per sequence.  One
query per sequence attends over all valid pages with online softmax.

Decode is the one-token chunk of ``chunked_prefill_paged``: the query
sits at position ``length - 1`` and sees every key before it, so the same
kernel (its block layout, scalar-prefetched block table and masking)
serves both.  Each grid step takes every query head against one page
with all its KV heads, and skips the pages past a row's length.  Its
calls are named
``chunked_prefill_paged_decode``, so a device trace tells decode from
chunk time and still matches both under ``chunked_prefill_paged``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chunked_prefill import chunked_prefill_paged


def paged_attention(
    q, k_pages, v_pages, lengths, *,
    softmax_scale: float | None = None,
    block_tables=None,
    layer=None,
    interpret: bool = False,
):
    """q: [B,H,D]; k/v pages: [B,P,page,Hkv,D]; lengths: [B] -> out [B,H,D].

    With ``block_tables`` [B,P], k/v are instead a shared page pool
    [N,page,Hkv,D] -- or with ``layer`` the stacked pool [L,N,page,Hkv,D],
    read at that layer -- and each sequence's pages are resolved through
    its block-table row (scalar prefetch).  Without, the per-sequence
    pages are viewed as a pool of ``B*P`` pages with an arithmetic table.
    A row with ``lengths == 0`` returns zeros."""
    if block_tables is None:
        b, p = k_pages.shape[:2]
        k_pages = k_pages.reshape((b * p,) + k_pages.shape[2:])
        v_pages = v_pages.reshape((b * p,) + v_pages.shape[2:])
        block_tables = jnp.arange(b * p, dtype=jnp.int32).reshape(b, p)
    lengths = lengths.astype(jnp.int32)
    out = chunked_prefill_paged(
        q[:, None], k_pages, v_pages, lengths, block_tables, lengths - 1,
        layer=layer, softmax_scale=softmax_scale,
        name="chunked_prefill_paged_decode",
        interpret=interpret,
    )
    return out[:, 0]
