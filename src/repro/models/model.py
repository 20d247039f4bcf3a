"""Composable model assembly for all assigned architecture families.

One ``Model`` wraps a ``ModelConfig`` and exposes pure functions:

* ``init(key)``                          -- parameter pytree (stacked layers)
* ``forward(params, tokens, ...)``       -- full-sequence logits (train/prefill)
* ``train_loss(params, batch)``          -- mean CE (+ MoE aux, + MTP)
* ``decode_step(params, cache, tok, pos)`` -- one-token serve step over the
  decode cache (the tensor SkyMemory blocks/chunks/stripes)

Layers are stacked (leading dim = n_layers) and driven by ``lax.scan`` so
96-layer dry-runs lower quickly; heterogeneous stacks (deepseek dense
prefix, zamba2 shared-attention periods) are segmented scans.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import maybe_shard
from repro.models import cache as cache_lib
from repro.models.attention import (
    attention_decode,
    attention_decode_paged,
    attention_prefill,
    attention_prefill_paged,
    init_attention,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    cross_entropy_loss,
    embed_tokens,
    init_embed,
    init_mlp,
    init_norm,
    unembed,
)
from repro.models.mla import init_mla, mla_decode, mla_prefill
from repro.models.moe import init_moe, moe_forward
from repro.models.ssd import init_ssd, ssd_decode, ssd_prefill


def _stacked(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _slice_layers(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def _remat(fn, policy: str | None):
    if policy is None or policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots
        )
    if policy == "dots_no_batch":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    raise ValueError(f"unknown remat policy {policy!r}")


class Model:
    def __init__(self, cfg: ModelConfig, *, unroll: bool = False):
        self.cfg = cfg
        # Fully unroll layer scans: used by the dry-run so XLA cost
        # analysis counts every layer (scan bodies are costed once).
        self.unroll = unroll

    def _scan(self, body, init, xs):
        if not self.unroll:
            return jax.lax.scan(body, init, xs)
        length = jax.tree.leaves(xs)[0].shape[0]
        if length == 1:
            # a length-1 scan still lowers to a while loop (which blocks
            # SPMD sharding propagation); inline the body instead
            x1 = jax.tree.map(lambda a: a[0], xs)
            carry, y = body(init, x1)
            return carry, jax.tree.map(lambda a: a[None], y)
        return jax.lax.scan(body, init, xs, unroll=True)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        params: dict[str, Any] = {"embed": init_embed(ks[0], cfg)}

        if cfg.arch_type in ("ssm", "hybrid"):
            params["blocks"] = _stacked(
                lambda k: self._init_ssm_block(k), ks[1], cfg.num_layers
            )
            if cfg.arch_type == "hybrid":
                params["shared_attn"] = {
                    "norm": init_norm(cfg),
                    "attn": init_attention(ks[2], cfg),
                }
        elif cfg.use_mla and cfg.first_k_dense:
            params["blocks_dense"] = _stacked(
                lambda k: self._init_block(k, moe=False), ks[1], cfg.first_k_dense
            )
            params["blocks"] = _stacked(
                lambda k: self._init_block(k, moe=True),
                ks[2],
                cfg.num_layers - cfg.first_k_dense,
            )
        else:
            moe = cfg.num_experts > 0
            params["blocks"] = _stacked(
                lambda k: self._init_block(k, moe=moe), ks[1], cfg.num_layers
            )

        if cfg.is_encoder_decoder:
            params["encoder"] = {
                "blocks": _stacked(
                    lambda k: self._init_block(k, moe=False),
                    ks[3],
                    cfg.num_encoder_layers,
                ),
                "norm": init_norm(cfg),
            }
            params["cross"] = _stacked(
                lambda k: {"norm": init_norm(cfg), "attn": init_attention(k, cfg)},
                ks[4],
                cfg.num_layers,
            )
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": jax.vmap(
                    lambda k: jax.random.normal(k, (2 * cfg.d_model, cfg.d_model))
                    * (2 * cfg.d_model) ** -0.5
                )(jax.random.split(ks[5], cfg.mtp_depth)).astype(cfg.dtype),
                "blocks": _stacked(
                    lambda k: self._init_block(k, moe=False), ks[6], cfg.mtp_depth
                ),
                "norm": init_norm(cfg),
            }
        params["final_norm"] = init_norm(cfg)
        return params

    def _init_block(self, key, *, moe: bool) -> dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        p = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
        p["attn"] = init_mla(k1, cfg) if cfg.use_mla else init_attention(k1, cfg)
        if moe:
            p["moe"] = init_moe(k2, cfg)
        else:
            p["mlp"] = init_mlp(k2, cfg)
        return p

    def _init_ssm_block(self, key) -> dict:
        return {"norm1": init_norm(self.cfg), "ssd": init_ssd(key, self.cfg)}

    # ------------------------------------------------------------------
    # embedding / frontends
    # ------------------------------------------------------------------
    def embed(self, params, tokens, *, image_embeds=None, frames=None):
        """Token embeddings; VLM prepends (stubbed) patch embeddings."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, cfg)
        if cfg.arch_type == "vlm" and image_embeds is not None:
            x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
        return maybe_shard(x, "act_btd")

    # ------------------------------------------------------------------
    # full-sequence forward (training / prefill)
    # ------------------------------------------------------------------
    def forward(
        self,
        params,
        tokens,
        *,
        image_embeds=None,
        frames=None,
        q_offset: int = 0,
        sliding_window: int | None = None,
        collect_state: bool = False,
        remat: str | None = None,
        prefix_state=None,
    ):
        """Returns (logits, aux_loss, state) -- ``state`` is the stacked
        per-layer decode state when ``collect_state`` (prefill), else None.
        ``prefix_state`` feeds a SkyMemory-restored prefix (chunked prefill:
        dense K/V prefix or SSM state snapshot)."""
        cfg = self.cfg
        x = self.embed(params, tokens, image_embeds=image_embeds)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, frames, remat=remat)

        if cfg.arch_type in ("ssm", "hybrid"):
            x, aux, state = self._ssm_stack(
                params, x, q_offset=q_offset,
                sliding_window=sliding_window,
                collect_state=collect_state, remat=remat,
                prefix_state=prefix_state,
            )
        else:
            x, aux, state = self._attn_stack(
                params, x, enc_out=enc_out, q_offset=q_offset,
                sliding_window=sliding_window,
                collect_state=collect_state, remat=remat,
                prefix_state=prefix_state,
            )
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)
        logits = maybe_shard(logits, "logits")
        return logits, aux, state

    def _encode(self, params, frames, *, remat=None):
        cfg = self.cfg

        def block(p, x):
            h = apply_norm(p["norm1"], x, cfg)
            a, _ = attention_prefill(p["attn"], h, cfg, causal=False)
            x = x + a
            h = apply_norm(p["norm2"], x, cfg)
            x = x + apply_mlp(p["mlp"], h, cfg)
            return maybe_shard(x, "act_btd")

        blk = _remat(lambda p, x: (block(p, x), None), remat)

        def body(x, p):
            y, _ = blk(p, x)
            return y, None

        x, _ = self._scan(body, frames.astype(jnp.dtype(cfg.dtype)),
                            params["encoder"]["blocks"])
        return apply_norm(params["encoder"]["norm"], x, cfg)

    def _attn_block(self, p, x, *, enc_out, cross_p, q_offset, sliding_window,
                    moe: bool, prefix_kv=None):
        cfg = self.cfg
        h = apply_norm(p["norm1"], x, cfg)
        if cfg.use_mla:
            latent_prefix = (
                (prefix_kv["ckv"], prefix_kv["kr"]) if prefix_kv else None
            )
            a, kv = mla_prefill(p["attn"], h, cfg, q_offset=q_offset,
                                sliding_window=sliding_window,
                                latent_prefix=latent_prefix)
        else:
            kv_prefix = (
                (prefix_kv["k"], prefix_kv["v"]) if prefix_kv else None
            )
            a, kv = attention_prefill(
                p["attn"], h, cfg, q_offset=q_offset,
                sliding_window=sliding_window, kv_cache=kv_prefix,
            )
        x = x + a
        if enc_out is not None and cross_p is not None:
            hc = apply_norm(cross_p["norm"], x, cfg)
            c, cross_kv = attention_prefill(
                cross_p["attn"], hc, cfg, kv_x=enc_out, causal=False
            )
            x = x + c
            kv = kv + cross_kv  # (k, v, ck, cv)
        h2 = apply_norm(p["norm2"], x, cfg)
        aux = jnp.float32(0.0)
        if moe:
            y, aux = moe_forward(p["moe"], h2, cfg)
        else:
            y = apply_mlp(p["mlp"], h2, cfg)
        x = maybe_shard(x + y, "act_btd")
        return x, aux, kv

    def _attn_stack(self, params, x, *, enc_out, q_offset, sliding_window,
                    collect_state, remat, prefix_state=None):
        cfg = self.cfg

        def run_scan(blocks, x, *, moe, cross=None, prefix=None):
            def blk_fn(p, x, cross_p, pref):
                return self._attn_block(
                    p, x, enc_out=enc_out, cross_p=cross_p,
                    q_offset=q_offset, sliding_window=sliding_window,
                    moe=moe, prefix_kv=pref,
                )

            blk = _remat(blk_fn, remat)

            def body(carry, xs):
                x, aux = carry
                y, a, kv = blk(xs["p"], x, xs.get("c"), xs.get("pref"))
                return (y, aux + a), (kv if collect_state else None)

            xs = {"p": blocks}
            if cross is not None:
                xs["c"] = cross
            if prefix is not None:
                xs["pref"] = prefix
            (x, aux), kvs = self._scan(body, (x, jnp.float32(0.0)), xs)
            return x, aux, kvs

        state = {}
        if cfg.use_mla and cfg.first_k_dense:
            k = cfg.first_k_dense
            mla_prefix = prefix_state.get("mla") if prefix_state else None
            pre_d = _slice_layers(mla_prefix, 0, k) if mla_prefix else None
            pre_m = (_slice_layers(mla_prefix, k, cfg.num_layers)
                     if mla_prefix else None)
            x, aux1, kv1 = run_scan(params["blocks_dense"], x, moe=False,
                                    prefix=pre_d)
            x, aux2, kv2 = run_scan(params["blocks"], x, moe=True,
                                    prefix=pre_m)
            total_aux = aux1 + aux2
            if collect_state:
                state["mla"] = {
                    "ckv": jnp.concatenate([kv1[0], kv2[0]], axis=0),
                    "kr": jnp.concatenate([kv1[1], kv2[1]], axis=0),
                }
        else:
            moe = cfg.num_experts > 0
            cross = params.get("cross")
            prefix = None
            if prefix_state:
                prefix = prefix_state.get("mla") or prefix_state.get("kv")
            x, total_aux, kvs = run_scan(
                params["blocks"], x, moe=moe, cross=cross, prefix=prefix
            )
            if collect_state and kvs is not None:
                if cfg.use_mla:
                    state["mla"] = {"ckv": kvs[0], "kr": kvs[1]}
                elif cfg.is_encoder_decoder:
                    state["kv"] = {"k": kvs[0], "v": kvs[1]}
                    state["cross"] = {"k": kvs[2], "v": kvs[3]}
                else:
                    state["kv"] = {"k": kvs[0], "v": kvs[1]}
        return x, total_aux, (state if collect_state else None)

    def _ssm_stack(self, params, x, *, q_offset, sliding_window,
                   collect_state, remat, prefix_state=None):
        cfg = self.cfg

        def ssm_block(p, x, pref):
            h = apply_norm(p["norm1"], x, cfg)
            y, st = ssd_prefill(p["ssd"], h, cfg, state=pref)
            return maybe_shard(x + y, "act_btd"), st

        blk = _remat(ssm_block, remat)

        def segment(blocks, x, prefix):
            def body(carry, xs):
                y, st = blk(xs["p"], carry, xs.get("pref"))
                return y, st if collect_state else None

            xs = {"p": blocks}
            if prefix is not None:
                xs["pref"] = prefix
            return self._scan(body, x, xs)

        state: dict = {}
        if cfg.arch_type == "hybrid" and cfg.attn_layer_period:
            period = cfg.attn_layer_period
            n_attn = cfg.num_layers // period
            sts, kvs_k, kvs_v = [], [], []
            lo = 0
            for j in range(n_attn):
                hi = lo + period
                seg_prefix = (
                    _slice_layers(prefix_state["ssm"], lo, hi)
                    if prefix_state else None
                )
                x, st = segment(
                    _slice_layers(params["blocks"], lo, hi), x, seg_prefix
                )
                if collect_state:
                    sts.append(st)
                # shared attention block (weights reused every period)
                sp = params["shared_attn"]
                h = apply_norm(sp["norm"], x, cfg)
                pref_kv = None
                if prefix_state and "kv" in prefix_state:
                    pref_kv = (
                        prefix_state["kv"]["k"][j],
                        prefix_state["kv"]["v"][j],
                    )
                a, kv = attention_prefill(
                    sp["attn"], h, cfg, q_offset=q_offset,
                    sliding_window=sliding_window, kv_cache=pref_kv,
                )
                x = maybe_shard(x + a, "act_btd")
                if collect_state:
                    kvs_k.append(kv[0])
                    kvs_v.append(kv[1])
                lo = hi
            if lo < cfg.num_layers:
                seg_prefix = (
                    _slice_layers(prefix_state["ssm"], lo, cfg.num_layers)
                    if prefix_state else None
                )
                x, st = segment(
                    _slice_layers(params["blocks"], lo, cfg.num_layers),
                    x, seg_prefix,
                )
                if collect_state:
                    sts.append(st)
            if collect_state:
                state["ssm"] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *sts
                )
                state["kv"] = {
                    "k": jnp.stack(kvs_k, axis=0),
                    "v": jnp.stack(kvs_v, axis=0),
                }
        else:
            prefix = prefix_state["ssm"] if prefix_state else None
            x, st = segment(params["blocks"], x, prefix)
            if collect_state:
                state["ssm"] = st
        return x, jnp.float32(0.0), (state if collect_state else None)

    # ------------------------------------------------------------------
    # training loss
    # ------------------------------------------------------------------
    def train_loss(self, params, batch, *, remat: str | None = None):
        cfg = self.cfg
        tokens = batch["tokens"]
        targets = batch["targets"]
        logits, aux, _ = self.forward(
            params,
            tokens,
            image_embeds=batch.get("image_embeds"),
            frames=batch.get("frames"),
            sliding_window=cfg.sliding_window or None,
            remat=remat,
        )
        n_img = 0
        if cfg.arch_type == "vlm" and batch.get("image_embeds") is not None:
            n_img = batch["image_embeds"].shape[1]
            logits = logits[:, n_img:]
        loss = cross_entropy_loss(logits[:, :-1], targets[:, 1:])
        metrics = {"ce": loss, "aux": aux}
        if cfg.mtp_depth and "mtp" in params:
            loss = loss + 0.3 * self._mtp_loss(params, logits, tokens, targets)
        total = loss + aux
        metrics["loss"] = total
        return total, metrics

    def _mtp_loss(self, params, logits, tokens, targets):
        """DeepSeek-V3 multi-token prediction: one extra depth predicting
        token t+2 from [h_t ; emb(token_{t+1})] (simplified single block)."""
        cfg = self.cfg
        del logits
        x = self.embed(params, tokens)
        emb_next = jnp.roll(x, -1, axis=1)
        h = jnp.concatenate([x, emb_next], axis=-1)
        proj = params["mtp"]["proj"][0]
        h = (h @ proj).astype(x.dtype)
        blk = _slice_layers(params["mtp"]["blocks"], 0, 1)
        p0 = jax.tree.map(lambda a: a[0], blk)
        h2, _, _ = (
            self._attn_block(
                p0, h, enc_out=None, cross_p=None, q_offset=0,
                sliding_window=None, moe=False,
            )
        )
        h2 = apply_norm(params["mtp"]["norm"], h2, cfg)
        lg = unembed(params["embed"], h2, cfg)
        return cross_entropy_loss(lg[:, :-2], targets[:, 2:])

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int, *, specs_only=False,
                   src_len=None):
        return cache_lib.init_cache(
            self.cfg, batch, seq_len, specs_only=specs_only, src_len=src_len
        )

    @property
    def supports_paged_decode(self) -> bool:
        return cache_lib.supports_paged_decode(self.cfg)

    def init_paged_cache(self, *, num_slots: int, page_size: int,
                         max_seq_len: int, num_pages: int | None = None):
        return cache_lib.PagedKVCache(
            self.cfg, num_slots=num_slots, page_size=page_size,
            max_seq_len=max_seq_len, num_pages=num_pages,
        )

    def decode_step_paged(self, params, k_pool, v_pool, tokens,
                          block_tables, lengths, *, contiguous=False):
        """One continuous-batching serve step over the shared page pool.

        ``tokens`` [B,1] at per-sequence absolute positions ``lengths`` [B]
        (heterogeneous: slots admit mid-decode); ``k_pool``/``v_pool`` are
        ``[L, N_pages, page, Hkv, hd]``; ``block_tables`` [B, P] maps each
        slot's logical pages to pool pages (``None`` with
        ``contiguous=True``, where slot regions make page ids arithmetic).
        Returns (logits [B,1,V], k_pool', v_pool').  Dense-attention
        families only (``supports_paged_decode``).
        """
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, cfg)

        # The stacked pools ride the scan CARRY whole: each layer writes its
        # tokens into them in place and attention reads its layer's pages
        # straight from them -- no layer is sliced out or written back, and
        # a ys-stacked scan would materialize a full copy of the cache.
        def body(carry, l):
            x, kp, vp = carry
            p = jax.tree.map(lambda a: a[l], params["blocks"])
            h = apply_norm(p["norm1"], x, cfg)
            a, kp, vp = attention_decode_paged(
                p["attn"], h, cfg, k_pool=kp, v_pool=vp, layer=l,
                block_tables=block_tables, lengths=lengths,
                contiguous=contiguous,
            )
            x = x + a
            h2 = apply_norm(p["norm2"], x, cfg)
            if cfg.num_experts > 0:
                y, _ = moe_forward(p["moe"], h2, cfg)
            else:
                y = apply_mlp(p["mlp"], h2, cfg)
            return (x + y, kp, vp), None

        carry = (x, k_pool, v_pool)
        if self.unroll:
            for l in range(cfg.num_layers):
                carry, _ = body(carry, l)
        else:
            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(cfg.num_layers))
        x, ks, vs = carry
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)
        return logits, ks, vs

    def prefill_chunk_paged(self, params, k_pool, v_pool, tokens,
                            block_tables, q_offsets, n_valid):
        """A batch of prefill *chunks* over the shared page pool.

        ``tokens`` [R, C] holds one prompt slice per row, row ``i``
        starting at absolute position ``q_offsets[i]`` (``n_valid[i] <= C``
        real tokens; the tail is padding, and an all-padding row with
        ``n_valid == 0`` is a no-op).  Each layer writes the chunks' K/V
        into their slots' pool pages (through ``block_tables`` [R, P]) and
        attends over everything cached so far -- SkyMemory-restored pages,
        earlier chunks, and this chunk -- read in place from the pool.
        Returns ``(last_logits [R, V], k_pool', v_pool')`` -- only each
        row's last *valid* position is unembedded (the one logit a
        finishing chunk samples its first token from; a C x V projection
        per step would be pure waste on a serving vocabulary).
        ``q_offsets``/``n_valid`` are traced, so one compilation per
        buffer shape serves every chunk of every admission; this is the
        half of the engine's fused mixed step that retires prompt tokens
        (decode_step_paged retires generation tokens), and the whole of
        its cold-start admission wave.  Dense attention families only
        (``supports_paged_decode``).
        """
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens, cfg)

        # pools ride the scan carry whole, exactly like decode_step_paged
        def body(carry, l):
            x, kp, vp = carry
            p = jax.tree.map(lambda a: a[l], params["blocks"])
            h = apply_norm(p["norm1"], x, cfg)
            a, kp, vp = attention_prefill_paged(
                p["attn"], h, cfg, k_pool=kp, v_pool=vp, layer=l,
                block_tables=block_tables, q_offsets=q_offsets,
                n_valid=n_valid,
            )
            x = x + a
            h2 = apply_norm(p["norm2"], x, cfg)
            if cfg.num_experts > 0:
                y, _ = moe_forward(p["moe"], h2, cfg)
            else:
                y = apply_mlp(p["mlp"], h2, cfg)
            return (x + y, kp, vp), None

        carry = (x, k_pool, v_pool)
        if self.unroll:
            for l in range(cfg.num_layers):
                carry, _ = body(carry, l)
        else:
            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(cfg.num_layers))
        x, ks, vs = carry
        x = apply_norm(params["final_norm"], x, cfg)
        idx = jnp.maximum(jnp.asarray(n_valid, jnp.int32) - 1, 0)   # [R]
        last = jnp.take_along_axis(
            x, idx[:, None, None], axis=1)                  # [R, 1, D]
        logits = unembed(params["embed"], last, cfg)[:, 0]  # [R, V]
        return logits, ks, vs

    def decode_step(self, params, cache, tokens, pos):
        """One serve step: ``tokens`` [B,1] at absolute position ``pos``
        (scalar); returns (logits [B,1,V], new_cache)."""
        cfg = self.cfg
        swin = cfg.sliding_window or None
        x = embed_tokens(params["embed"], tokens, cfg)
        new_cache = dict(cache)

        if cfg.arch_type in ("ssm", "hybrid"):
            x, new_cache = self._ssm_decode(params, x, cache, pos)
        elif cfg.use_mla:
            x, new_cache = self._mla_decode(params, x, cache, pos, swin)
        else:
            x, new_cache = self._attn_decode(params, x, cache, pos, swin)
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["embed"], x, cfg)
        return logits, new_cache

    def _attn_decode(self, params, x, cache, pos, swin):
        cfg = self.cfg
        cross = params.get("cross")

        def body(x, xs):
            p = xs["p"]
            h = apply_norm(p["norm1"], x, cfg)
            a, k, v = attention_decode(
                p["attn"], h, cfg, k_cache=xs["k"], v_cache=xs["v"],
                pos=pos, sliding_window=swin,
            )
            x = x + a
            if cross is not None:
                hc = apply_norm(xs["c"]["norm"], x, cfg)
                cx, _, _ = attention_decode(
                    xs["c"]["attn"], hc, cfg, k_cache=xs["k"], v_cache=xs["v"],
                    pos=pos, cross_kv=(xs["ck"], xs["cv"]),
                )
                x = x + cx
            h2 = apply_norm(p["norm2"], x, cfg)
            if cfg.num_experts > 0:
                y, _ = moe_forward(p["moe"], h2, cfg)
            else:
                y = apply_mlp(p["mlp"], h2, cfg)
            return x + y, (k, v)

        xs = {"p": params["blocks"], "k": cache["kv"]["k"], "v": cache["kv"]["v"]}
        if cross is not None:
            xs["c"] = cross
            xs["ck"] = cache["cross"]["k"]
            xs["cv"] = cache["cross"]["v"]
        x, (ks, vs) = self._scan(body, x, xs)
        new_cache = dict(cache)
        new_cache["kv"] = {"k": ks, "v": vs}
        return x, new_cache

    def _mla_decode(self, params, x, cache, pos, swin):
        cfg = self.cfg

        def make_body(moe):
            def body(x, xs):
                p = xs["p"]
                h = apply_norm(p["norm1"], x, cfg)
                a, ckv, kr = mla_decode(
                    p["attn"], h, cfg, ckv_cache=xs["ckv"],
                    krope_cache=xs["kr"], pos=pos, sliding_window=swin,
                )
                x = x + a
                h2 = apply_norm(p["norm2"], x, cfg)
                if moe:
                    y, _ = moe_forward(p["moe"], h2, cfg)
                else:
                    y = apply_mlp(p["mlp"], h2, cfg)
                return x + y, (ckv, kr)
            return body

        mla = cache["mla"]
        new_cache = dict(cache)
        if cfg.first_k_dense:
            k = cfg.first_k_dense
            x, (c1, r1) = self._scan(
                make_body(False), x,
                {"p": params["blocks_dense"], "ckv": mla["ckv"][:k],
                 "kr": mla["kr"][:k]},
            )
            x, (c2, r2) = self._scan(
                make_body(True), x,
                {"p": params["blocks"], "ckv": mla["ckv"][k:],
                 "kr": mla["kr"][k:]},
            )
            new_cache["mla"] = {
                "ckv": jnp.concatenate([c1, c2], axis=0),
                "kr": jnp.concatenate([r1, r2], axis=0),
            }
        else:
            x, (c, r) = self._scan(
                make_body(cfg.num_experts > 0), x,
                {"p": params["blocks"], "ckv": mla["ckv"], "kr": mla["kr"]},
            )
            new_cache["mla"] = {"ckv": c, "kr": r}
        return x, new_cache

    def _ssm_decode(self, params, x, cache, pos):
        cfg = self.cfg
        swin = cfg.sliding_window or None

        def body(x, xs):
            p = xs["p"]
            h = apply_norm(p["norm1"], x, cfg)
            y, conv, st = ssd_decode(
                p["ssd"], h, cfg, conv_state=xs["conv"], ssm_state=xs["state"]
            )
            return x + y, (conv, st)

        ssm = cache["ssm"]
        new_cache = dict(cache)
        if cfg.arch_type == "hybrid" and cfg.attn_layer_period:
            period = cfg.attn_layer_period
            n_attn = cfg.num_layers // period
            convs, states, ks, vs = [], [], [], []
            lo = 0
            kvc = cache["kv"]
            for j in range(n_attn):
                hi = lo + period
                xs = {
                    "p": _slice_layers(params["blocks"], lo, hi),
                    "conv": ssm["conv"][lo:hi],
                    "state": ssm["state"][lo:hi],
                }
                x, (cv, st) = self._scan(body, x, xs)
                convs.append(cv)
                states.append(st)
                sp = params["shared_attn"]
                h = apply_norm(sp["norm"], x, cfg)
                a, k, v = attention_decode(
                    sp["attn"], h, cfg, k_cache=kvc["k"][j], v_cache=kvc["v"][j],
                    pos=pos, sliding_window=swin,
                )
                x = x + a
                ks.append(k)
                vs.append(v)
                lo = hi
            if lo < cfg.num_layers:
                xs = {
                    "p": _slice_layers(params["blocks"], lo, cfg.num_layers),
                    "conv": ssm["conv"][lo:],
                    "state": ssm["state"][lo:],
                }
                x, (cv, st) = self._scan(body, x, xs)
                convs.append(cv)
                states.append(st)
            new_cache["ssm"] = {
                "conv": jnp.concatenate(convs, axis=0),
                "state": jnp.concatenate(states, axis=0),
            }
            new_cache["kv"] = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
        else:
            xs = {"p": params["blocks"], "conv": ssm["conv"], "state": ssm["state"]}
            x, (cv, st) = self._scan(body, x, xs)
            new_cache["ssm"] = {"conv": cv, "state": st}
        return x, new_cache
