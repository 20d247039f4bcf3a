"""jit'd kernel entry points with implementation dispatch.

``impl``:
  * ``"auto"``    -- compiled Pallas kernels on a TPU backend, the jnp
                     oracle on any other (the CPU test runs);
  * ``"jnp"``     -- the ref.py oracle;
  * ``"pallas"``  -- the Pallas TPU kernel (compiled on TPU, interpret=True
                     elsewhere, so kernel logic is testable on the CPU).

Override globally with the ``REPRO_KERNEL_IMPL`` environment variable.
"""
from __future__ import annotations

import os
from functools import partial

import jax

from repro.kernels import ref


def _resolve(impl: str) -> str:
    impl = os.environ.get("REPRO_KERNEL_IMPL", impl)
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(
    q, k, v, *,
    causal: bool = True,
    q_offset=0,
    sliding_window: int | None = None,
    lengths=None,
    softmax_scale: float | None = None,
    impl: str = "auto",
):
    """Prefill/chunked-prefill attention ([B,Sq,H,D] x [B,Skv,Hkv,D])."""
    impl = _resolve(impl)
    if impl == "jnp":
        if (lengths is None and k.shape[1] >= ref.STREAMING_KV_THRESHOLD):
            # memory-realistic path for long sequences: never materialize
            # the full score matrix (mirrors the TPU flash kernel)
            return ref.attention_streaming_ref(
                q, k, v, causal=causal, q_offset=q_offset,
                sliding_window=sliding_window, softmax_scale=softmax_scale,
                block_k=ref.STREAMING_BLOCK_K,
            )
        return ref.attention_ref(
            q, k, v, causal=causal, q_offset=q_offset,
            sliding_window=sliding_window, lengths=lengths,
            softmax_scale=softmax_scale,
        )
    from repro.kernels import chunked_prefill

    return chunked_prefill.chunked_prefill_attention(
        q, k, v, causal=causal, q_offset=q_offset,
        sliding_window=sliding_window, lengths=lengths,
        softmax_scale=softmax_scale, interpret=_interpret(),
    )


def paged_attention(
    q, k_pages, v_pages, lengths, *,
    softmax_scale: float | None = None,
    block_tables=None,
    layer=None,
    grouped: bool | None = None,
    impl: str = "auto",
):
    """Decode attention over a paged KV cache ([B,H,D] x [B,P,page,Hkv,D]).

    ``block_tables`` [B,P] switches to the shared-pool layout: k/v are
    [N,page,Hkv,D] and pages are resolved per sequence through the table
    (the serving engine's device-resident layout); with ``layer`` they
    are the stacked pool [L,N,page,Hkv,D], read at that layer in place.
    ``grouped`` forces the jnp oracle's grouped-GQA contraction (no
    head-repeat materialization); the Pallas kernel is always grouped by
    construction.
    """
    impl = _resolve(impl)
    if impl == "jnp":
        if layer is not None:
            k_pages, v_pages = k_pages[layer], v_pages[layer]
        return ref.paged_attention_ref(
            q, k_pages, v_pages, lengths, softmax_scale=softmax_scale,
            block_tables=block_tables, grouped=grouped,
        )
    from repro.kernels import paged_attention as pa

    return pa.paged_attention(
        q, k_pages, v_pages, lengths,
        softmax_scale=softmax_scale, block_tables=block_tables, layer=layer,
        interpret=_interpret(),
    )


def chunked_prefill_paged(
    q, k_pool, v_pool, lengths, block_tables, q_offsets, *,
    layer=None,
    softmax_scale: float | None = None,
    impl: str = "auto",
):
    """Prefill-chunk attention over a shared page pool ([B,Sq,H,D] x
    [N,page,Hkv,D] through [B,P] block tables; with ``layer``, the stacked
    pool [L,N,page,Hkv,D] read at that layer).

    The serving engine's chunked-prefill read path: a chunk's queries at
    absolute offset ``q_offsets`` attend causally over the first
    ``lengths`` pool tokens of their sequence -- SkyMemory-restored pages
    and earlier chunks are read in place (scalar-prefetched tables on
    TPU; grouped-GQA gather oracle elsewhere).  Offsets/lengths are
    runtime values, so one compilation serves every chunk of a prefill.
    """
    impl = _resolve(impl)
    if impl == "jnp":
        if layer is not None:
            k_pool, v_pool = k_pool[layer], v_pool[layer]
        return ref.chunked_prefill_paged_ref(
            q, k_pool, v_pool, lengths, block_tables, q_offsets,
            softmax_scale=softmax_scale,
        )
    from repro.kernels import chunked_prefill

    return chunked_prefill.chunked_prefill_paged(
        q, k_pool, v_pool, lengths, block_tables, q_offsets, layer=layer,
        softmax_scale=softmax_scale, interpret=_interpret(),
    )


def ssd_scan(
    x, dt, a, b_mat, c_mat, *,
    chunk_size: int = 64,
    initial_state=None,
    impl: str = "auto",
):
    """Mamba-2 SSD chunked scan ([B,L,H,P] -> y, final_state)."""
    impl = _resolve(impl)
    if impl == "jnp":
        return ref.ssd_scan_ref(
            x, dt, a, b_mat, c_mat,
            chunk_size=chunk_size, initial_state=initial_state,
        )
    from repro.kernels import ssd_scan as sk

    return sk.ssd_chunk_scan(
        x, dt, a, b_mat, c_mat,
        chunk_size=chunk_size, initial_state=initial_state,
        interpret=_interpret(),
    )


ssd_decode_step = ref.ssd_decode_step_ref  # tiny op: jnp everywhere
attention = partial(flash_attention)
