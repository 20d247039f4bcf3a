"""Decode-state containers (the tensors SkyMemory blocks and stripes).

Two layouts:

* ``init_cache``      -- dense per-sequence caches (dict of arrays), used by
  training-side tooling and the non-paged decode families (MLA latents, SSM
  state, encoder-decoder cross K/V).  Plain pytrees so they pjit/shard
  cleanly; ``specs_only`` returns ShapeDtypeStructs for the dry-run.
* ``PagedKVCache``    -- the serving engine's device-resident page pool for
  dense-attention families.  Pages are ``page_size`` tokens (= the
  SkyMemory block size), allocated from a shared free list and addressed
  through per-slot block tables, so constellation-fetched blocks drop
  straight into pages and freed pages are recycled mid-decode
  (continuous batching).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.models.config import ModelConfig

KVC_INT8_SCALE = 1.0 / 32.0  # symmetric int8 KVC quantization step


def quant_kvc(x):
    return jnp.clip(jnp.round(x / KVC_INT8_SCALE), -127, 127).astype(jnp.int8)


def dequant_kvc(x, dtype):
    return (x.astype(jnp.float32) * KVC_INT8_SCALE).astype(dtype)


def _make(shape, dtype, specs_only: bool):
    if specs_only:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def n_attn_layers(cfg: ModelConfig) -> int:
    return sum(1 for i in range(cfg.num_layers) if cfg.is_attn_layer(i))


def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Ring-buffer length: the sliding window if configured, else seq_len."""
    if cfg.sliding_window and cfg.sliding_window < seq_len:
        return cfg.sliding_window
    return seq_len


def init_cache(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    *,
    specs_only: bool = False,
    src_len: int | None = None,
):
    """Decode cache for one model family.

    dense/moe/vlm -> paged K/V; MLA -> latent; ssm -> fixed state;
    hybrid -> ssm state + K/V for the shared-attention invocations;
    audio (enc-dec) -> decoder self K/V + frozen cross K/V.
    """
    dt = jnp.dtype(cfg.kvc_dtype or cfg.dtype)
    s = cache_len(cfg, seq_len)
    cache: dict = {}

    if cfg.use_mla:
        la = cfg.num_layers
        cache["mla"] = {
            "ckv": _make((la, batch, s, cfg.kv_lora_rank), dt, specs_only),
            "kr": _make((la, batch, s, cfg.qk_rope_head_dim), dt, specs_only),
        }
    elif cfg.arch_type in ("ssm", "hybrid"):
        lm = cfg.num_layers
        conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        cache["ssm"] = {
            "conv": _make((lm, batch, cfg.ssm_conv - 1, conv_dim),
                          jnp.dtype(cfg.dtype), specs_only),
            "state": _make(
                (lm, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32, specs_only,
            ),
        }
        if cfg.arch_type == "hybrid":
            na = n_attn_layers(cfg)
            cache["kv"] = {
                "k": _make((na, batch, s, cfg.num_kv_heads, cfg.head_dim), dt,
                           specs_only),
                "v": _make((na, batch, s, cfg.num_kv_heads, cfg.head_dim), dt,
                           specs_only),
            }
    else:
        la = cfg.num_layers
        cache["kv"] = {
            "k": _make((la, batch, s, cfg.num_kv_heads, cfg.head_dim), dt,
                       specs_only),
            "v": _make((la, batch, s, cfg.num_kv_heads, cfg.head_dim), dt,
                       specs_only),
        }

    if cfg.is_encoder_decoder:
        ss = src_len if src_len is not None else s
        la = cfg.num_layers
        cache["cross"] = {
            "k": _make((la, batch, ss, cfg.num_kv_heads, cfg.head_dim), dt,
                       specs_only),
            "v": _make((la, batch, ss, cfg.num_kv_heads, cfg.head_dim), dt,
                       specs_only),
        }
    return cache


def supports_paged_decode(cfg: ModelConfig) -> bool:
    """True for the families whose decode state is plain per-token K/V --
    the ones the paged pool + paged-attention kernel can serve.  MLA
    latents, SSM state, encoder-decoder cross K/V, and sliding-window ring
    buffers keep the dense layout (a later PR can page the MLA latent)."""
    return (
        cfg.arch_type not in ("ssm", "hybrid")
        and not cfg.use_mla
        and not cfg.is_encoder_decoder
        and not cfg.sliding_window
    )


class PagedKVCache:
    """Shared K/V page pool + per-slot block tables (dense-attn families).

    Device state: ``k_pool`` / ``v_pool`` of shape
    ``[layers, num_pages, page_size, kv_heads, head_dim]``.  Host state:
    an int32 ``block_tables`` [slots, pages_per_seq] mapping each slot's
    logical page index to a pool page.  Two allocation modes:

    * **contiguous** (default, full-size pool): slot ``s`` permanently
      owns pages ``[s*P, (s+1)*P)``, so per layer the pool *is*
      ``[slots, P, page, Hkv, hd]`` by reshape -- decode attention reads
      it with zero gather (the contiguous paged kernel / oracle), and the
      decode write's page id is ``s*P + pos//page``, needing no table on
      device.  An idle slot's unconditional decode write lands at its own
      region's page 0, which the next admission overwrites.
    * **free-list** (explicit ``num_pages``, e.g. oversubscribed pools):
      pages come from a shared free list; page 0 is a reserved scratch
      page that idle slots' rows point at; attention goes through the
      block-table (scalar-prefetch) kernel path.

    The pool arrays are replaced functionally (the jitted decode step
    returns updated pools; the engine donates them so backends update in
    place); the allocator is host-side bookkeeping only.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        num_slots: int,
        page_size: int,
        max_seq_len: int,
        num_pages: int | None = None,
    ) -> None:
        if not supports_paged_decode(cfg):
            raise ValueError(f"{cfg.name}: family has no paged decode layout")
        self.cfg = cfg
        self.page_size = page_size
        self.num_slots = num_slots
        self.pages_per_seq = -(-max_seq_len // page_size)
        self.contiguous = num_pages is None
        if self.contiguous:
            self.num_pages = num_slots * self.pages_per_seq
        else:
            self.num_pages = num_pages
            if self.num_pages < 1 + self.pages_per_seq:
                raise ValueError("pool smaller than one sequence")
        self.dtype = jnp.dtype(cfg.kvc_dtype or cfg.dtype)
        shape = (cfg.num_layers, self.num_pages, page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        self.k_pool = jnp.zeros(shape, self.dtype)
        self.v_pool = jnp.zeros(shape, self.dtype)
        p = self.pages_per_seq
        if self.contiguous:
            self._free = []
            self.block_tables = np.asarray(
                [[s * p + j for j in range(p)] for s in range(num_slots)],
                np.int32)
            self._slot_pages = [list(row) for row in self.block_tables]
            self._slot_free = [True] * num_slots
        else:
            # page 0 reserved as scratch -- never on the free list
            self._free = list(range(self.num_pages - 1, 0, -1))
            self.block_tables = np.zeros((num_slots, p), np.int32)
            self._slot_pages = [[] for _ in range(num_slots)]
        # partial-prefill write cursor: tokens of the slot's sequence
        # covered by pages so far (restored blocks + retired chunks) --
        # chunked prefill advances it span by span, and span bookkeeping
        # rejects gaps/overlap bugs before they corrupt the pool
        self.cursors = [0] * num_slots

    # -- allocator ------------------------------------------------------
    @property
    def free_pages(self) -> int:
        if self.contiguous:
            return sum(self._slot_free) * self.pages_per_seq
        return len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        """Enough free pages to reserve ``n_tokens`` tokens up front.

        The engine reserves a sequence's *worst-case* footprint (prompt +
        max_new_tokens, capped at max_seq_len) at admission, so a running
        sequence can never hit pool exhaustion mid-decode -- an admitted
        request always completes.  Unused reserved pages return to the
        pool at release (early EOS)."""
        if self.contiguous:
            return (any(self._slot_free)
                    and self.pages_for(n_tokens) <= self.pages_per_seq)
        return len(self._free) >= self.pages_for(n_tokens)

    def ensure_capacity(self, slot: int, n_tokens: int) -> bool:
        """Allocate pages until ``slot`` can hold ``n_tokens`` tokens.
        Returns True when the block table changed (caller re-uploads it)."""
        need = self.pages_for(n_tokens)
        if need > self.pages_per_seq:
            raise RuntimeError(
                f"slot {slot}: {n_tokens} tokens exceeds "
                f"{self.pages_per_seq} pages per sequence")
        if self.contiguous:
            self._slot_free[slot] = False
            return False                 # fixed region: table never changes
        pages = self._slot_pages[slot]
        changed = False
        while len(pages) < need:
            if not self._free:
                raise RuntimeError("KV page pool exhausted")
            pid = self._free.pop()
            self.block_tables[slot, len(pages)] = pid
            pages.append(pid)
            changed = True
        return changed

    def pages_allocated(self, slot: int) -> int:
        """Pages currently backing ``slot`` (contiguous regions always own
        their full span; free-list slots grow lazily)."""
        if self.contiguous:
            return self.pages_per_seq
        return len(self._slot_pages[slot])

    def export_pages(self, slot: int, n_pages: int):
        """Offload view: the slot's first ``n_pages`` pages as host arrays
        ``[layers, n_pages, page_size, kv_heads, head_dim]``.

        ONE gathered device read per pool (then a single device->host
        transfer each), not a round trip per page -- the export half of
        preemption-by-offload, where a victim sequence's K/V moves to the
        host tier so its pool pages can be reassigned.  ``write_pages`` is
        the exact inverse; an export/import round trip is bit-identical
        (int8 pools move as raw int8)."""
        ids = self._slot_pages[slot][:n_pages]
        if len(ids) != n_pages:
            raise RuntimeError(
                f"slot {slot}: export of {n_pages} pages exceeds "
                f"{len(ids)} allocated")
        idx = jnp.asarray(ids, jnp.int32)
        return (np.asarray(self.k_pool[:, idx]),
                np.asarray(self.v_pool[:, idx]))

    def free_slot(self, slot: int) -> None:
        """Return the slot's pages to the pool (free-list mode repoints
        the slot at the scratch page)."""
        self.cursors[slot] = 0
        if self.contiguous:
            self._slot_free[slot] = True
            return
        self._free.extend(reversed(self._slot_pages[slot]))
        self._slot_pages[slot] = []
        self.block_tables[slot, :] = 0

    # -- partial-prefill write cursors ----------------------------------
    def table_row(self, slot: int) -> np.ndarray:
        """The slot's block-table row [pages_per_seq] -- what a chunked
        prefill uploads so the chunk can resolve its own page ids on
        device (contiguous mode rows are the arithmetic region ids)."""
        return self.block_tables[slot]

    def note_span(self, slot: int, start: int, n_tokens: int) -> None:
        """Record that tokens ``[start, start + n_tokens)`` of the slot's
        sequence are now (being) written to its pages -- the device-side
        chunk scatter does the actual write.  Rewriting already-covered
        positions is allowed (the whole-prompt-cached replay recomputes
        the final token in place); a *gap* past the cursor is a scheduler
        bug and raises before the pool is corrupted."""
        if start > self.cursors[slot]:
            raise RuntimeError(
                f"slot {slot}: span start {start} leaves a gap past write "
                f"cursor {self.cursors[slot]}")
        end = start + n_tokens
        if self.pages_for(end) > len(self._slot_pages[slot]):
            raise RuntimeError(
                f"slot {slot}: span end {end} beyond allocated pages")
        self.cursors[slot] = max(self.cursors[slot], end)

    # -- page writes (jitted, pools donated) ----------------------------
    def write_pages(self, slot: int, first_page: int, k_blocks, v_blocks):
        """Drop whole pages into the pool: ``k_blocks``/``v_blocks`` are
        ``[layers, n_pages, page_size, kv_heads, head_dim]`` -- e.g. blocks
        fetched from the constellation, already page-shaped.  One jitted
        scatter that donates both pools and writes them in place, so a
        restore never holds a second pool.  Spans are padded to a power of
        two pages (padding rows target a page past the pool and are
        dropped), so a handful of compilations serve every span."""
        n = k_blocks.shape[1]
        ids = self._slot_pages[slot][first_page:first_page + n]
        if len(ids) != n:
            raise RuntimeError("write_pages beyond allocated pages")
        k_blocks, v_blocks = self._cast(k_blocks), self._cast(v_blocks)
        width = pl.next_power_of_2(n)
        pad = ((0, 0), (0, width - n), (0, 0), (0, 0), (0, 0))
        self.k_pool, self.v_pool = _put_pages(
            self.k_pool, self.v_pool,
            jnp.asarray(ids + [self.num_pages] * (width - n), jnp.int32),
            jnp.pad(k_blocks, pad), jnp.pad(v_blocks, pad))
        self.cursors[slot] = max(self.cursors[slot],
                                 (first_page + n) * self.page_size)

    def write_token_span(self, slot: int, start: int, k, v):
        """Write ``k``/``v`` ``[layers, n_tokens, kv_heads, head_dim]`` at
        token offset ``start`` (must be page-aligned: spans start where a
        fetched-block prefix ended).  The tail is zero-padded to a page
        boundary; the per-sequence length masks it."""
        if start % self.page_size:
            raise ValueError("span start must be page-aligned")
        la, n, hkv, hd = k.shape
        pad = (-n) % self.page_size
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nb = k.shape[1] // self.page_size
        shape = (la, nb, self.page_size, hkv, hd)
        self.write_pages(slot, start // self.page_size,
                         k.reshape(shape), v.reshape(shape))
        self.cursors[slot] = start + n   # the padded tail is not real data

    def _cast(self, x):
        x = jnp.asarray(x)
        if self.dtype == jnp.int8 and x.dtype != jnp.int8:
            return quant_kvc(x)
        return x.astype(self.dtype)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _put_pages(k_pool, v_pool, ids, k_blocks, v_blocks):
    """Pages ``[:, i]`` of the blocks into pool pages ``ids[i]``; ids past
    the pool are dropped."""
    return (k_pool.at[:, ids].set(k_blocks, mode="drop"),
            v_pool.at[:, ids].set(v_blocks, mode="drop"))


def cache_bytes(cfg: ModelConfig, batch: int, seq_len: int) -> int:
    specs = init_cache(cfg, batch, seq_len, specs_only=True)
    return sum(
        int(jnp.prod(jnp.array(leaf.shape))) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(specs)
    )
