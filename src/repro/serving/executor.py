"""Executors: every jitted device program the serving stack launches.

``PagedExecutor`` owns the paged-runtime programs -- the fused decode
step, the mixed decode+chunk step, the cold-start chunk wave, the dense
prefill used by stop-the-world admission -- plus the PRNG stream and the
compile-shape policies (chunk buffers, length buckets).  It reads and
writes K/V through the L0 pool held by the ``TieredKVManager``; the
scheduler never touches device arrays directly.

``DenseRuntime`` is the non-paged serving loop for the families whose
decode state is not plain per-token K/V (MLA latents, SSM state, hybrid,
encoder-decoder): dense batched caches, the vectorized sampler, one host
sync per step.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.request import Seq, SeqState, seq_finished, seq_result
from repro.serving.sampler import SamplingParams, sample_batch, stack_sampling
from repro.serving.stats import EngineStats
from repro.serving.tokenizer import truncate_prompt


class PagedExecutor:
    """Jitted mixed decode/prefill steps, sampling, and device state."""

    def __init__(self, model, params, pool, *, chunk_tokens: int,
                 max_seq_len: int, seed: int = 0) -> None:
        self.model = model
        self.params = params
        self.pool = pool
        self.cfg = model.cfg
        self.chunk_tokens = chunk_tokens
        self.max_seq_len = max_seq_len
        self._key = jax.random.PRNGKey(seed)
        # pools are donated: on backends with donation support the
        # one-token write updates the cache in place instead of copying
        # the whole pool every step (CPU falls back to copy)
        self._step = jax.jit(self._paged_step,
                             static_argnames=("mode",),
                             donate_argnums=(1, 2))
        self._mixed = jax.jit(self._mixed_step,
                              static_argnames=("mode",),
                              donate_argnums=(1, 2))
        # cold-start admission waves: batched chunk steps (nothing is
        # decoding, so the whole wave prefills together)
        self._chunk_wave = jax.jit(self.model.prefill_chunk_paged,
                                   donate_argnums=(1, 2))
        self._prefill = jax.jit(
            lambda p, t: self.model.forward(p, t, collect_state=True)
        )

    def next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    # -- the fused device programs --------------------------------------
    def _decode_sample(self, params, k_pool, v_pool, block_tables, lengths,
                       tokens, key, temps, top_ks, top_ps, mode):
        """Decode every slot and sample its next token: the shared tail of
        the plain and mixed steps.

        ``mode`` is decided host-side from the *active slots'* sampling
        params (it only changes on admission/finish, so at most a few
        compilations): ``greedy`` is a pure argmax, ``temp`` skips the
        top-k/top-p sort machinery, ``full`` runs the general sampler.
        """
        logits, k_pool, v_pool = self.model.decode_step_paged(
            params, k_pool, v_pool, tokens[:, None], block_tables, lengths,
            contiguous=self.pool.contiguous,
        )
        lg = logits[:, 0]
        if mode == "greedy":
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        elif mode == "temp":
            lg32 = lg.astype(jnp.float32)
            greedy = jnp.argmax(lg32, axis=-1).astype(jnp.int32)
            is_greedy = temps <= 0.0
            scaled = lg32 / jnp.where(is_greedy, 1.0, temps)[:, None]
            sampled = jax.random.categorical(key, scaled, -1).astype(jnp.int32)
            nxt = jnp.where(is_greedy, greedy, sampled)
        else:
            nxt = sample_batch(lg, key, temps, top_ks, top_ps)
        return nxt, k_pool, v_pool

    def _paged_step(self, params, k_pool, v_pool, block_tables, lengths,
                    tokens, key, temps, top_ks, top_ps, *, mode):
        """One fused decode step: model + sampler, one device program."""
        return self._decode_sample(params, k_pool, v_pool, block_tables,
                                   lengths, tokens, key, temps, top_ks,
                                   top_ps, mode)

    def _mixed_step(self, params, k_pool, v_pool, block_tables, lengths,
                    tokens, key, temps, top_ks, top_ps,
                    c_toks, c_bt, c_off, c_valid, c_temp, c_tk, c_tp,
                    *, mode):
        """One fused mixed step: a prefill chunk rides the decode step.

        The chunk (``c_toks`` [1, C] at absolute offset ``c_off``,
        ``c_valid`` real tokens) writes its K/V into pool pages and
        attends over the restored prefix + earlier chunks in place; then
        every slot decodes exactly as in the plain step, so running
        sequences never stall for an admission.  If this is the
        sequence's final chunk, its first output token is the extra id
        sampled here from the last valid chunk logit -- returned as row
        ``B`` of the token vector so the host still does ONE sync.
        ``c_off``/``c_valid`` are traced, so one compilation serves every
        chunk of every admission (no power-of-two prefill buckets).
        """
        kd, kc = jax.random.split(key)
        c_logits, k_pool, v_pool = self.model.prefill_chunk_paged(
            params, k_pool, v_pool, c_toks, c_bt, c_off, c_valid)
        c_tid = sample_batch(c_logits, kc, c_temp, c_tk, c_tp)
        nxt, k_pool, v_pool = self._decode_sample(
            params, k_pool, v_pool, block_tables, lengths, tokens, kd,
            temps, top_ks, top_ps, mode)
        return jnp.concatenate([nxt, c_tid]), k_pool, v_pool

    # -- scheduler-facing wrappers (pool updated in place) --------------
    def step(self, bt_d, len_d, tok_d, temps, tks, tps, mode,
             chunk_ops=None):
        """Launch one fused step; returns the device token vector (the
        caller's ``np.asarray`` is the step's single host sync)."""
        k = self.next_key()
        if chunk_ops is None:
            nxt, kp, vp = self._step(
                self.params, self.pool.k_pool, self.pool.v_pool,
                bt_d, len_d, tok_d, k, temps, tks, tps, mode=mode)
        else:
            nxt, kp, vp = self._mixed(
                self.params, self.pool.k_pool, self.pool.v_pool,
                bt_d, len_d, tok_d, k, temps, tks, tps,
                *chunk_ops, mode=mode)
        self.pool.k_pool, self.pool.v_pool = kp, vp
        return nxt

    def chunk_wave(self, buf, bts, offs, valids):
        """One lockstep batched chunk step (cold-start admission wave)."""
        lg, kp, vp = self._chunk_wave(
            self.params, self.pool.k_pool, self.pool.v_pool,
            jnp.asarray(buf), jnp.asarray(bts), jnp.asarray(offs),
            jnp.asarray(valids),
        )
        self.pool.k_pool, self.pool.v_pool = kp, vp
        return lg

    def prefill_chunk_one(self, tokens_row, bt_row, start: int, v: int):
        """A single chunk over the pool (stop-the-world suffix prefill and
        restore-tail replay): the chunk-wave program on one row, its
        buffer padded to a length bucket so a few compilations serve every
        span."""
        buf = np.zeros((1, self.bucket(v)), np.int32)
        buf[0, :v] = np.asarray(tokens_row).reshape(-1)[:v]
        return self.chunk_wave(buf, bt_row, np.asarray([start], np.int32),
                               np.asarray([v], np.int32))[0]

    def prefill_dense(self, toks):
        """Batched bucketed dense prefill (stop-the-world misses)."""
        return self._prefill(self.params, toks)

    def prefill_exact(self, tokens: list[int]):
        """Unpadded, per-sequence prefill (MoE families, where padding
        would perturb capacity-based routing of real tokens).  Returns
        (last_logits, state)."""
        toks = jnp.asarray(tokens, jnp.int32)[None]
        lg, _, state = self.model.forward(
            self.params, toks, collect_state=True)
        return lg[0, len(tokens) - 1], state

    def sample_first(self, logits_rows, samplings) -> np.ndarray:
        """First tokens for an admission wave: one call, one host sync."""
        t_arr, tk_arr, tp_arr = stack_sampling(samplings)
        return np.asarray(sample_batch(
            jnp.stack(logits_rows), self.next_key(), t_arr, tk_arr, tp_arr))

    # -- compile-shape policy -------------------------------------------
    @staticmethod
    def sampler_mode(samp: list[SamplingParams]) -> str:
        if any(p.top_k > 0 or p.top_p < 1.0 for p in samp
               if p.temperature > 0.0):
            return "full"
        if any(p.temperature > 0.0 for p in samp):
            return "temp"
        return "greedy"

    def chunk_buf(self, v: int) -> int:
        """Chunk-buffer length for ``v`` valid tokens: the next power of
        two (floor 32), capped at the chunk budget.  Short prompts and
        ragged final chunks don't pay for a full-budget buffer, and the
        compile count is bounded by the (small) budget instead of
        max_seq_len -- the legacy O(log^2) whole-prompt buckets reduce to
        a handful of chunk-sized shapes."""
        b = 32
        while b < v:
            b *= 2
        return min(b, max(self.chunk_tokens, v))

    def bucket(self, n: int) -> int:
        """Prefill length bucket for stop-the-world admission (next power
        of two, floor 32, capped at max_seq_len).  The chunked scheduler
        needs no buckets: its one fixed chunk shape serves every prompt."""
        b = 32
        while b < n:
            b *= 2
        return min(b, max(n, self.max_seq_len))


class DenseRuntime:
    """Non-paged serving loop (MLA / SSM / hybrid / enc-dec families):
    dense batched caches with the vectorized sampler and one host sync
    per step.  Shares the SkyMemory protocol objects with the paged path
    but not the page pool -- paging these decode states is future work."""

    def __init__(self, model, params, tokenizer, adapter, manager, *,
                 max_seq_len: int, max_batch: int, write_back: bool,
                 seed: int = 0) -> None:
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.tokenizer = tokenizer
        self.adapter = adapter
        self.manager = manager
        self.max_seq_len = max_seq_len
        self.max_batch = max_batch
        self.write_back = write_back
        self.stats = EngineStats()
        self._key = jax.random.PRNGKey(seed)
        self._decode = jax.jit(model.decode_step)
        self._sample = jax.jit(sample_batch)

    def generate(self, requests) -> list:
        results = []
        for lo in range(0, len(requests), self.max_batch):
            results.extend(self._run_batch(requests[lo: lo + self.max_batch]))
        return results

    def _make_seq(self, req) -> Seq:
        tokens = truncate_prompt(self.tokenizer.encode(req.prompt),
                                 self.max_seq_len)
        return Seq(request=req, tokens=tokens, enqueue_t=time.perf_counter())

    def _prefill_one(self, req) -> Seq:
        s = self._make_seq(req)
        tokens = s.tokens
        cached = 0
        prefix_state = None
        if self.manager is not None:
            payload, cached = self.manager.get_cache_tokens(tokens)
            if payload is not None:
                prefix_state = self.adapter.payload_to_state(payload)
        toks = jnp.asarray(tokens, jnp.int32)[None]
        if cached >= len(tokens):
            # whole prompt cached: replay the final token so the decode
            # loop has a starting distribution
            cached = len(tokens) - 1
        if cached:
            lg, _, state = self.model.forward(
                self.params, toks[:, cached:], q_offset=cached,
                prefix_state=prefix_state, collect_state=True,
            )
        else:
            lg, _, state = self.model.forward(
                self.params, toks, collect_state=True
            )
        self.stats.cached_tokens += cached
        self.stats.prefilled_tokens += len(tokens) - cached
        if self.write_back and self.manager is not None:
            self.manager.add_blocks_tokens(tokens)
        s.cached = cached
        s.dense_state = state
        s.last_logits = lg[0, -1]
        s.state = SeqState.RUNNING
        return s

    def _stack_dense_caches(self, seqs: list[Seq]):
        """Dense prefill->decode handoff: per-sequence states are
        restacked into one batched cache.  Paged families never come here
        -- their blocks were written into pool pages at admission."""
        cache = self.model.init_cache(len(seqs), self.max_seq_len)
        for i, s in enumerate(seqs):
            n = len(s.tokens)
            st = s.dense_state
            if "kv" in st and "kv" in cache:
                cache["kv"]["k"] = cache["kv"]["k"].at[:, i, :n].set(
                    st["kv"]["k"][:, 0, :n])
                cache["kv"]["v"] = cache["kv"]["v"].at[:, i, :n].set(
                    st["kv"]["v"][:, 0, :n])
            if "mla" in st:
                cache["mla"]["ckv"] = cache["mla"]["ckv"].at[:, i, :n].set(
                    st["mla"]["ckv"][:, 0, :n])
                cache["mla"]["kr"] = cache["mla"]["kr"].at[:, i, :n].set(
                    st["mla"]["kr"][:, 0, :n])
            if "ssm" in st:
                cache["ssm"]["conv"] = cache["ssm"]["conv"].at[:, i].set(
                    st["ssm"]["conv"][:, 0])
                cache["ssm"]["state"] = cache["ssm"]["state"].at[:, i].set(
                    st["ssm"]["state"][:, 0].astype(cache["ssm"]["state"].dtype))
        return cache

    def _run_batch(self, requests) -> list:
        t_start = time.perf_counter()
        seqs = [self._prefill_one(r) for r in requests]
        cache = self._stack_dense_caches(seqs)
        pos = jnp.asarray([len(s.tokens) for s in seqs], jnp.int32)

        # first token of each sequence from its prefill logits
        logits = jnp.stack([s.last_logits for s in seqs])
        temps_d, tks_d, tps_d = stack_sampling(
            [s.request.sampling for s in seqs])

        max_new = max(s.request.sampling.max_new_tokens for s in seqs)
        t_dec = time.perf_counter()
        first = True
        last_tok_t = [0.0] * len(seqs)
        for _step in range(max_new):
            self._key, k = jax.random.split(self._key)
            nxt = self._sample(logits, k, temps_d, tks_d, tps_d)
            nxt_h = np.asarray(nxt)           # the step's single host sync
            now = time.perf_counter()
            for i, s in enumerate(seqs):
                if s.done:
                    continue
                tid = int(nxt_h[i])
                s.out_ids.append(tid)
                if first:
                    s.ttft_s = now - s.enqueue_t
                    self.stats.ttft_s.append(s.ttft_s)
                else:
                    self.stats.itl_s.append(now - last_tok_t[i])
                last_tok_t[i] = now
                seq_finished(s, tid, eos_id=self.tokenizer.eos_id,
                             max_seq_len=self.max_seq_len)
            first = False
            self.stats.decoded_tokens += sum(
                0 if s.done else 1 for s in seqs)
            if all(s.done for s in seqs):
                break
            lg, cache = self._decode(self.params, cache, nxt[:, None], pos)
            self.stats.decode_steps += 1
            logits = lg[:, 0]
            pos = pos + 1
        self.stats.decode_time_s += time.perf_counter() - t_dec

        out = []
        wall = time.perf_counter() - t_start
        for s in seqs:
            self.stats.requests += 1
            s.state = SeqState.FINISHED
            s.wall_s = wall
            out.append(seq_result(s, self.tokenizer))
        return out
