"""Adapter between model decode state and SkyMemory KVC payloads.

The protocol (core/) moves opaque bytes; this adapter defines what those
bytes are per architecture family (DESIGN.md §4):

* dense/vlm/moe : per-layer K/V covering the cached prefix (cumulative, as
                  the paper's Get step 7 retrieves a single block whose
                  payload reconstructs the full prefix KVC);
* MLA           : compressed latent (c_kv, k_rope) -- ~14x smaller blocks;
* ssm/hybrid    : fixed-size (conv_state, ssm_state) snapshot at the block
                  boundary (+ shared-attn K/V for hybrids).

``kvc_fn`` plugs into ``core.protocol.KVCManager``: it computes one block's
payload by resuming from the previous block's payload -- never recomputing
the already-cached prefix (the compute saving the paper measures).

``codec=`` (a ``core.chunking.PayloadCodec``, or its string spec) shapes
what the payload bytes *are*: f32 ships the arrays verbatim (legacy wire
format), int8/int4 quantize with per-block-chunk scale tables, and
``+delta`` makes each dense cumulative block carry only its own
``block_size`` tokens plus a back-pointer (the KVC manager reassembles
the chain on restore).  Decoding is always codec-agnostic -- payloads
are self-describing -- so mixed-codec fabrics restore fine.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunking import (
    PayloadCodec,
    decode_payload_arrays,
    make_delta_payload,
)
from repro.core.hashing import chain_hashes
from repro.models.model import Model
from repro.trace import span


class SkyKVCAdapter:
    def __init__(self, model: Model, params, *,
                 codec: "PayloadCodec | str | None" = None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.codec = PayloadCodec.parse(codec) if not isinstance(
            codec, PayloadCodec) else codec
        # delta chains concatenate along the token axis, which only the
        # dense/vlm/moe cumulative K/V payload has end to end; SSM
        # snapshots and hybrid state are not token-sliceable
        self._delta_ok = (not self.cfg.use_mla
                          and self.cfg.arch_type not in ("ssm", "hybrid"))
        self._executor = None    # lazy fetch-ahead worker (pages_async)

    # -- codec-derived size model (the router's fallback price) -----------
    def payload_bytes_per_token(self) -> float | None:
        """Encoded payload bytes one cached token costs under this
        adapter's codec -- the size model the router falls back to when a
        block has no registered ``payload_bytes``.  None for families
        whose payload is not token-linear (SSM/hybrid snapshots)."""
        cfg = self.cfg
        if cfg.arch_type in ("ssm", "hybrid"):
            return None
        if cfg.use_mla:
            values = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        else:
            values = 2 * cfg.num_kv_heads * cfg.head_dim
        values *= cfg.num_layers
        itemsize = jnp.dtype(cfg.dtype).itemsize
        return values * self.codec.bytes_per_value(itemsize)

    # -- state <-> payload ------------------------------------------------
    def state_to_payload(self, state: dict, n_tokens: int, *,
                         past_len: int = 0,
                         prev_hash: bytes | None = None) -> bytes:
        """Serialize the decode state for the first ``n_tokens`` positions
        (state arrays carry a batch dim of 1, dropped in the payload).

        Under a ``+delta`` codec, a dense-family block that extends a
        chain (``past_len > 0`` with ``prev_hash``) serializes only its
        own ``[past_len:n_tokens]`` token slice behind a back-pointer --
        the O(1)-byte Set; everything else stays cumulative."""
        delta = (self.codec.delta and self._delta_ok
                 and past_len > 0 and prev_hash is not None)
        lo = past_len if delta else 0
        arrs: list[np.ndarray] = []
        if "ssm" in state:
            arrs.append(np.asarray(state["ssm"]["conv"][:, 0]))
            arrs.append(np.asarray(state["ssm"]["state"][:, 0]))
        if "mla" in state:
            arrs.append(np.asarray(state["mla"]["ckv"][:, 0, :n_tokens]))
            arrs.append(np.asarray(state["mla"]["kr"][:, 0, :n_tokens]))
        if "kv" in state:
            arrs.append(np.asarray(state["kv"]["k"][:, 0, lo:n_tokens]))
            arrs.append(np.asarray(state["kv"]["v"][:, 0, lo:n_tokens]))
        inner = self.codec.encode(arrs)
        if delta:
            return make_delta_payload(inner, prev_hash, past_len)
        return inner

    def payload_to_state(self, payload: bytes) -> dict:
        cfg = self.cfg
        arrs = decode_payload_arrays(payload)
        state: dict = {}
        i = 0
        if cfg.arch_type in ("ssm", "hybrid"):
            state["ssm"] = {
                "conv": jnp.asarray(arrs[i])[:, None],
                "state": jnp.asarray(arrs[i + 1])[:, None],
            }
            i += 2
        if cfg.use_mla:
            state["mla"] = {
                "ckv": jnp.asarray(arrs[i])[:, None],
                "kr": jnp.asarray(arrs[i + 1])[:, None],
            }
            i += 2
        if i < len(arrs):
            state["kv"] = {
                "k": jnp.asarray(arrs[i])[:, None],
                "v": jnp.asarray(arrs[i + 1])[:, None],
            }
        return state

    def payload_to_pages(self, payload: bytes, n_tokens: int,
                         page_size: int):
        """Dense-family payload -> page-shaped K/V blocks, ready to drop
        straight into a ``PagedKVCache`` pool (no dense restacking).

        Returns ``(k_blocks, v_blocks)`` of shape
        ``[layers, n_tokens/page, page, Hkv, hd]``.  ``n_tokens`` must be
        page-aligned -- SkyMemory prefixes always are, because the engine's
        page size equals the constellation block size.
        """
        cfg = self.cfg
        if cfg.use_mla or cfg.arch_type in ("ssm", "hybrid"):
            raise ValueError(f"{cfg.name}: payload is not plain paged K/V")
        if n_tokens % page_size:
            raise ValueError("cached prefix must be page-aligned")
        arrs = decode_payload_arrays(payload)
        k, v = arrs[0], arrs[1]                      # [L, n_cov, Hkv, hd]
        la, _, hkv, hd = k.shape
        nb = n_tokens // page_size
        shape = (la, nb, page_size, hkv, hd)
        return (
            jnp.asarray(k[:, :n_tokens]).reshape(shape),
            jnp.asarray(v[:, :n_tokens]).reshape(shape),
        )

    def pages_to_payload(self, k_blocks, v_blocks, n_tokens: int, *,
                         tokens: "Sequence[int] | None" = None) -> bytes:
        """Inverse of ``payload_to_pages``: page-shaped K/V blocks
        (``[layers, n_pages, page, Hkv, hd]``, e.g. a preempted sequence's
        exported pool pages) -> a dense-family KVC payload covering the
        first ``n_tokens`` positions.

        This is how the swap tier writes the constellation without model
        recompute: the pool pages already hold the exact K/V, so the
        payload is a reshape + codec encode.  Under the f32 codec (and
        for integer pools under any codec -- quantized codes are stored
        verbatim, so int8 pools stay int8) a later ``payload_to_pages``
        round trip returns the identical arrays.

        Under a ``+delta`` codec the caller passes the entry's
        ``tokens`` so the back-pointer hash of the preceding block can
        be recomputed from the chain: the payload for a block past the
        first then carries only its own token slice."""
        k = np.asarray(k_blocks)
        v = np.asarray(v_blocks)
        la, nb, page, hkv, hd = k.shape
        if n_tokens > nb * page:
            raise ValueError("n_tokens exceeds the exported pages")
        flat = (la, nb * page, hkv, hd)
        bt = self.codec.block_tokens
        lo = 0
        prev_hash = None
        if (self.codec.delta and self._delta_ok and tokens is not None
                and n_tokens > bt):
            lo = n_tokens - bt
            prev_hash = chain_hashes(list(tokens[:lo]), bt)[-1]
        inner = self.codec.encode([
            np.ascontiguousarray(k.reshape(flat)[:, lo:n_tokens]),
            np.ascontiguousarray(v.reshape(flat)[:, lo:n_tokens]),
        ])
        if prev_hash is not None:
            return make_delta_payload(inner, prev_hash, lo)
        return inner

    def pages_async(self, payload: bytes, n_tokens: int, page_size: int):
        """Fetch-ahead hook: decode a constellation payload into
        page-shaped K/V on a worker thread, returning a Future.

        The byte -> array deserialization is pure host work; submitting it
        here lets the engine keep its in-flight decode step (device
        compute) running while the payload decodes, instead of stalling
        the serving loop -- the communication/compute overlap the chunked
        scheduler exploits for the first fresh chunk after a SkyMemory
        hit.  ``.result()`` gives the same ``(k_blocks, v_blocks)`` as
        ``payload_to_pages``.
        """
        return self.run_async(
            self.payload_to_pages, payload, n_tokens, page_size)

    def run_async(self, fn, *args):
        """Run ``fn(*args)`` on the adapter's single worker thread.

        One worker serializes everything submitted here (payload decodes,
        Set KVC write-backs), so protocol-ordering guarantees -- a
        write-back lands before the next lookup that should hit it --
        survive the move off the engine's decode loop."""
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor

            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="skymem-fetch")
        return self._executor.submit(fn, *args)

    # -- the KVCManager hook ----------------------------------------------
    def kvc_fn(self, tokens: Sequence[int], past: bytes | None,
               past_len: int) -> bytes:
        """Payload for the block ending at len(tokens), resuming from
        ``past`` (a payload -- possibly a reassembled cat container --
        covering the first ``past_len`` tokens).  Under a ``+delta``
        codec the emitted payload carries only the new tokens plus a
        back-pointer recomputed from the token chain."""
        toks = jnp.asarray(list(tokens), jnp.int32)[None]
        if past is None or past_len == 0:
            past_len = 0
            prefix = None
        else:
            with span("write_back.resume"):
                prefix = self.payload_to_state(past)
        # the forward is eager: its span waits for the device, so the
        # encode after it times the host's read-back and codec alone
        with span("write_back.forward"):
            if prefix is None:
                _, _, state = self.model.forward(
                    self.params, toks, collect_state=True
                )
            else:
                _, _, state = self.model.forward(
                    self.params, toks[:, past_len:],
                    q_offset=past_len, prefix_state=prefix,
                    collect_state=True,
                )
                state = _concat_prefix(self.cfg, prefix, state, past_len)
            jax.block_until_ready(state)
        prev_hash = None
        if self.codec.delta and self._delta_ok and past_len > 0:
            prev_hash = chain_hashes(
                list(tokens[:past_len]), self.codec.block_tokens)[-1]
        with span("write_back.encode"):
            return self.state_to_payload(state, len(tokens),
                                         past_len=past_len,
                                         prev_hash=prev_hash)


def _concat_prefix(cfg, prefix: dict, state: dict, past_len: int) -> dict:
    """Stitch prefix K/V back in front of the freshly-computed suffix state.

    For dense families ``forward`` already returns K/V including the prefix
    (the prefix K/V were concatenated inside attention); for SSM the state
    is cumulative by construction; so this is only needed for hybrids' KV
    when the attention path did not include the prefix -- handled uniformly
    by checking lengths.
    """
    out = dict(state)
    if "kv" in state and "kv" in prefix:
        k = state["kv"]["k"]
        if k.shape[2] < past_len:  # suffix-only: prepend prefix
            out["kv"] = {
                "k": jnp.concatenate([prefix["kv"]["k"], k], axis=2),
                "v": jnp.concatenate([prefix["kv"]["v"], state["kv"]["v"]],
                                     axis=2),
            }
    return out
