"""Bring-up check of the served path on one TPU chip.

Run: python chip_smoke.py

One process drives, through the entry points a user calls, the serving
main path at TinyLlama-1.1B's published widths (22 layers, d 2048, GQA
32/4, head_dim 64, vocab 32000, bf16, random weights from a seed):
router -> ``Engine`` replicas -> paged runtime with chunked prefill ->
Set/Get KVC over the simulated 19x5 constellation.  Phases:

1. device: refuses to run unless JAX's first device is a TPU and
   ``REPRO_KERNEL_IMPL`` is unset (no oracle may stand in for a kernel);
2. kernels: the four Pallas kernels of the served path, compiled, at the
   served shapes, against their ``kernels/ref.py`` oracles -- the paged
   ones reading a stacked pool ``[L, N, page, Hkv, D]`` in place at its
   middle and last layer, as the step programs do, and one layer's pool;
3. closed batch: an ``EngineCluster`` of 2 replicas with block-table
   pools that together take over half of the HBM left free by the
   parameters serves 8 greedy requests sharing 128-token document blocks;
4. open stream: seeded multi-tenant arrivals through ``serve_stream`` in
   realtime mode;
5. serving checks: every request completed, constellation prefix hits,
   and a Pallas kernel in the decode, mixed and chunk-wave programs;
6. answers: every closed-batch request's first two served tokens against
   the greedy choice of the same prompts prefilled and decoded with the
   jnp oracle; then one prompt whose prefix the constellation holds is
   served again by a replica's own programs (Get KVC, page import, its
   chunk-wave program, its decode step at ``max_batch``), and those
   prefill and decode-step logits are held to the oracle's.

Each phase prints its findings on lines of its own, and the timings it
prints describe this one run, not a benchmark.  Any failure exits
non-zero; the last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# bf16 kernel outputs against the oracle, elementwise.  Sequences of 1, 2
# and page +- 1 tokens are among the rows, where one key more or less, or
# a page from the wrong slot, moves the output by far more than this.
KERNEL_TOL = dict(atol=3e-2, rtol=3e-2)
# served logits against the jnp-oracle step: relative L2 error.
LOGITS_TOL = 5e-2
# a served greedy token against the oracle's logits: at most this many
# standard deviations below the oracle's top logit.  bf16 noise moves a
# logit by about 0.05 std (the largest |served - oracle| over the
# vocabulary on a v5e); one restored page left out moves a smoke-width
# model's first token 0.65 std down.
GREEDY_MARGIN = 0.2
SENTENCE = ("SkyMemory keeps transformer KV caches in the memory of a low "
            "earth orbit constellation, one hop from any point on earth. ")


class SmokeFailure(RuntimeError):
    """A phase found the system misbehaving."""


@dataclasses.dataclass(frozen=True)
class Size:
    """What one run serves; ``full()`` is the chip run."""

    cfg: object
    replicas: int = 2
    block_size: int = 128
    max_seq_len: int = 2048
    max_batch: int = 8
    pool_share: float = 0.55   # of HBM free after params, all pools
    num_pages: int | None = None   # per replica, where no memory_stats
    chunk_bytes: int = 256 * 1024
    requests: int = 8
    doc_blocks: int = 4
    max_new_tokens: int = 32
    arrivals: int = 16
    arrival_rate: float = 4.0  # virtual requests per second
    stream_new_tokens: int = 16
    seed: int = 0

    @classmethod
    def full(cls) -> "Size":
        from repro.configs import get_config

        return cls(cfg=get_config("skymemory-tinyllama"))


def check_device() -> dict:
    """The chip this run is for, or ``SmokeFailure``."""
    if "REPRO_KERNEL_IMPL" in os.environ:
        raise SmokeFailure(
            "REPRO_KERNEL_IMPL is set: kernels must not be overridden")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# phase 2: kernels against the oracle
# ---------------------------------------------------------------------------

def kernels_phase(size: Size, *, compiled: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.chunked_prefill import (
        chunked_prefill_attention,
        chunked_prefill_paged,
    )
    from repro.kernels.paged_attention import paged_attention

    cfg = size.cfg
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    page, b = size.block_size, size.max_batch
    p = size.max_seq_len // page
    chunk = 2 * page                   # the engine's default chunk budget
    dt = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(size.seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dt)

    n_pages = b * p + 1
    layers = cfg.num_layers
    mid, last = layers // 2, layers - 1
    kp, vp = (rand(layers, n_pages, page, hkv, d) for _ in range(2))
    bt = jnp.asarray(rng.permutation(n_pages)[:b * p].reshape(b, p),
                     jnp.int32)
    edge = [0, 1, 2, page - 1, page, page + 1,
            size.max_seq_len // 2 + 3, size.max_seq_len]
    lens = jnp.asarray((edge * b)[:b], jnp.int32)
    q = rand(b, h, d)
    qc = rand(2, chunk, h, d)
    offs = jnp.asarray([0, page + 5], jnp.int32)
    q1 = rand(1, 1, h, d)
    off1 = jnp.asarray([2 * page - 1], jnp.int32)
    qd = rand(1, page, h, d)
    kd, vd = (rand(1, size.doc_blocks * page, hkv, d) for _ in range(2))
    prefix = (size.doc_blocks - 1) * page
    interpret = not compiled

    # the pools are arguments, not constants folded into the programs
    cases = {
        "paged_attention[block_table]": (
            lambda kp, vp: paged_attention(q, kp, vp, lens, block_tables=bt,
                                           layer=last, interpret=interpret),
            lambda kp, vp: ref.paged_attention_ref(
                q, kp[last], vp[last], lens, block_tables=bt)),
        "paged_attention[contiguous]": (
            lambda kp, vp: paged_attention(q, kp[mid][bt], vp[mid][bt], lens,
                                           interpret=interpret),
            lambda kp, vp: ref.paged_attention_ref(
                q, kp[mid][bt], vp[mid][bt], lens)),
        "chunked_prefill_paged[chunk]": (
            lambda kp, vp: chunked_prefill_paged(
                qc, kp, vp, offs + chunk, bt[:2], offs, layer=mid,
                interpret=interpret),
            lambda kp, vp: ref.chunked_prefill_paged_ref(
                qc, kp[mid], vp[mid], offs + chunk, bt[:2], offs)),
        "chunked_prefill_paged[replay]": (
            lambda kp, vp: chunked_prefill_paged(
                q1, kp, vp, off1 + 1, bt[:1], off1, layer=last,
                interpret=interpret),
            lambda kp, vp: ref.chunked_prefill_paged_ref(
                q1, kp[last], vp[last], off1 + 1, bt[:1], off1)),
        "chunked_prefill_attention": (
            lambda kp, vp: chunked_prefill_attention(
                qd, kd, vd, q_offset=prefix, interpret=interpret),
            lambda kp, vp: ref.attention_ref(qd, kd, vd, q_offset=prefix)),
    }
    for name, (kernel, oracle) in cases.items():
        got = np.asarray(jax.jit(kernel)(kp, vp), np.float32)
        want = np.asarray(jax.jit(oracle)(kp, vp), np.float32)
        err = float(np.abs(got - want).max())
        ok = bool(np.allclose(got, want, **KERNEL_TOL))
        _say("kernels", f"{name} {tuple(got.shape)} {dt.name}: "
                        f"max|kernel-oracle|={err!r} "
                        f"(atol {KERNEL_TOL['atol']}, rtol "
                        f"{KERNEL_TOL['rtol']}) {'ok' if ok else 'FAIL'}")
        _check(ok, f"kernel {name} disagrees with its oracle")


# ---------------------------------------------------------------------------
# phases 3-5: the served path
# ---------------------------------------------------------------------------

def _pages_per_replica(size: Size, params) -> tuple[int, int | None]:
    """Pool pages per replica, and the HBM free after the parameters."""
    import jax

    cfg = size.cfg
    jax.block_until_ready(params)
    stats = jax.devices()[0].memory_stats()
    if not stats:
        _check(size.num_pages is not None,
               "the device reports no memory_stats: give num_pages")
        return size.num_pages, None
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    itemsize = jax.numpy.dtype(cfg.dtype).itemsize
    page_bytes = (2 * cfg.num_layers * size.block_size * cfg.num_kv_heads
                  * cfg.head_dim * itemsize)
    return int(size.pool_share * free / (size.replicas * page_bytes)), free


def build_cluster(size: Size, model, params, num_pages: int):
    from repro.core import (
        ConstellationKVC,
        ConstellationSpec,
        IslTransport,
        LosWindow,
        Sat,
        SimClock,
        Strategy,
    )
    from repro.serving import EngineCluster

    spec = ConstellationSpec(num_planes=5, sats_per_plane=19,
                             altitude_km=550.0)    # the paper's testbed
    clock = SimClock(rate=10.0)
    kvc = ConstellationKVC(
        spec, LosWindow(Sat(2, 9), 5, 5), Strategy.ROTATION_HOP,
        num_servers=10, chunk_bytes=size.chunk_bytes,
        transport=IslTransport(spec, clock=clock,
                               chunk_processing_time_s=2e-4,
                               probe_timeout_s=5e-3),
    )
    return EngineCluster(
        model, params, kvc, num_replicas=size.replicas,
        block_size=size.block_size, max_seq_len=size.max_seq_len,
        max_batch=size.max_batch, num_pages=num_pages, rotate_every_s=2.0,
        seed=size.seed,
    )


def closed_requests(size: Size):
    from repro.serving import Request, SamplingParams

    doc_chars = size.doc_blocks * size.block_size + size.block_size // 2
    docs = [(f"[document {j}] " + SENTENCE * (doc_chars // len(SENTENCE) + 1)
             )[:doc_chars] for j in range(2)]
    sp = SamplingParams(max_new_tokens=size.max_new_tokens)
    return [Request(prompt=docs[i % 2] + f" Question {i}: what is cached?",
                    sampling=sp) for i in range(size.requests)]


def _check_results(phase: str, results, want_tokens: int) -> None:
    short = [r.request_id for r in results
             if len(r.token_ids) != want_tokens
             and r.finish_reason != "eos"]
    eos = sum(r.finish_reason == "eos" for r in results)
    _say(phase, f"{len(results)} completed, {len(short)} short of "
                f"{want_tokens} tokens, {eos} stopped at EOS")
    _check(not short, f"{phase}: requests {short} did not complete")


def closed_batch_phase(size: Size, cluster):
    reqs = closed_requests(size)
    t0 = time.perf_counter()
    results = cluster.serve(reqs)
    wall = time.perf_counter() - t0
    _check(len(results) == len(reqs) and all(r is not None for r in results),
           "closed batch: a request returned no result")
    _check_results("closed", results, size.max_new_tokens)
    fabric = cluster.fabric_stats()
    cached = sum(r.cached_tokens for r in results)
    _say("closed", f"constellation: block_hits={fabric['block_hits']} "
                   f"prefix_hit_rate={fabric['prefix_hit_rate']!r} "
                   f"cached_tokens={cached} "
                   f"blocks_set={fabric['blocks_set']}")
    _check(fabric["block_hits"] > 0 and cached > 0,
           "closed batch: no constellation prefix hit")
    tokens = sum(len(r.token_ids) for r in results)
    return results, tokens, wall


def stream_phase(size: Size, cluster):
    from repro.serving import SLO, TrafficGenerator, standard_tenants

    tenants = standard_tenants(3, size.arrival_rate,
                               max_new_tokens=size.stream_new_tokens)
    arrivals = TrafficGenerator(tenants, seed=size.seed).take(size.arrivals)
    report = cluster.serve_stream(
        arrivals, parallel=True,
        default_slo=SLO(ttft_s=10.0, itl_p95_s=1.0))
    shed = len(report.shed())
    results = report.results()
    _say("stream", f"{len(arrivals)} arrivals over "
                   f"{arrivals[-1].t_s!r} virtual s: {shed} shed, "
                   f"elapsed {report.elapsed_s!r} s")
    _check(shed == 0 and len(results) == len(arrivals),
           "stream: an arrival was shed or returned no result")
    _check_results("stream", results, size.stream_new_tokens)
    return report


def kernel_programs_phase(size: Size, cluster) -> None:
    """The decode, mixed and chunk-wave programs as the executor jits
    them must hold a compiled Pallas kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    eng = cluster.engines[0]
    ex, pool = eng.executor, eng.cache
    b, p, c = size.max_batch, pool.pages_per_seq, eng.chunk_tokens

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    key = jax.random.PRNGKey(0)
    decode = (ex.params, pool.k_pool, pool.v_pool, i32(b, p), i32(b), i32(b),
              key, f32(b), i32(b), f32(b))
    chunk = (i32(1, c), i32(1, p), i32(1), i32(1), f32(1), i32(1), f32(1))
    programs = {
        "decode": ex._step.lower(*decode, mode="greedy"),
        "mixed": ex._mixed.lower(*decode, *chunk, mode="greedy"),
        "chunk_wave": ex._chunk_wave.lower(
            ex.params, pool.k_pool, pool.v_pool, i32(2, c), i32(2, p),
            i32(2), i32(2)),
    }
    for name, lowered in programs.items():
        n = lowered.as_text().count("tpu_custom_call")
        _say("programs", f"{name}: {n} tpu_custom_call")
        _check(n > 0, f"{name} program holds no Pallas kernel")


# ---------------------------------------------------------------------------
# phase 6: served answers against the jnp oracle
# ---------------------------------------------------------------------------

def _prefill_rows(wave, pool, slots, rows, starts, budget, buf_len):
    """Lockstep chunk waves over ``pool``, as the scheduler runs a
    cold-start wave: row ``i`` prefills ``rows[i][starts[i]:]`` into slot
    ``slots[i]``, ``budget`` tokens a step in a buffer of ``buf_len(v)``.
    ``wave(buf, block_tables, offsets, valids)`` runs one step and updates
    the pool.  Returns each row's last-chunk logits, float32 ``[R, V]``."""
    import numpy as np

    cursors, last = list(starts), [None] * len(rows)
    while any(c < len(r) for c, r in zip(cursors, rows)):
        vs = [min(budget, len(r) - c) for c, r in zip(cursors, rows)]
        buf = np.zeros((len(rows), buf_len(max(vs))), np.int32)
        bts = np.stack([pool.table_row(s) for s in slots]).astype(np.int32)
        for i, (c, v) in enumerate(zip(cursors, vs)):
            if v > 0:
                buf[i, :v] = rows[i][c:c + v]
                pool.note_span(slots[i], c, v)
        lg = np.asarray(wave(buf, bts, np.asarray(cursors, np.int32),
                             np.asarray(vs, np.int32)), np.float32)
        for i, v in enumerate(vs):
            cursors[i] += v
            if v > 0 and cursors[i] >= len(rows[i]):
                last[i] = lg[i]
    return np.stack(last)


def oracle_logits(size: Size, model, params, rows, first, chunk_tokens,
                  buf_len):
    """Prefill logits of every prompt in ``rows`` and the logits of one
    decode step fed ``first[i]``, traced with the jnp oracle
    (``REPRO_KERNEL_IMPL=jnp`` set only around these jits, which wrap
    fresh lambdas so nothing is reused from a Pallas trace).  The shapes
    are the served ones: chunk buffers of ``buf_len`` and a decode batch
    of ``max_batch`` rows over ``[max_batch, pages_per_seq]`` tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b = size.max_batch
    per_row = -(-(max(map(len, rows)) + 1) // size.block_size)
    pool = model.init_paged_cache(num_slots=b, page_size=size.block_size,
                                  max_seq_len=size.max_seq_len,
                                  num_pages=1 + b * per_row)
    for i, r in enumerate(rows):
        pool.ensure_capacity(i, len(r) + 1)
    prev = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = "jnp"
    try:
        wave_fn = jax.jit(lambda *a: model.prefill_chunk_paged(*a),
                          donate_argnums=(1, 2))
        step_fn = jax.jit(lambda *a: model.decode_step_paged(*a),
                          donate_argnums=(1, 2))

        def wave(buf, bts, offs, valids):
            lg, pool.k_pool, pool.v_pool = wave_fn(
                params, pool.k_pool, pool.v_pool, *map(jnp.asarray, (
                    buf, bts, offs, valids)))
            return lg

        pre = _prefill_rows(wave, pool, list(range(len(rows))), rows,
                            [0] * len(rows), chunk_tokens, buf_len)
        toks = np.zeros((b, 1), np.int32)
        lens = np.zeros(b, np.int32)
        toks[:len(rows), 0] = first
        lens[:len(rows)] = [len(r) for r in rows]
        lg, pool.k_pool, pool.v_pool = step_fn(
            params, pool.k_pool, pool.v_pool, jnp.asarray(toks),
            jnp.asarray(pool.block_tables), jnp.asarray(lens))
    finally:
        if prev is None:
            del os.environ["REPRO_KERNEL_IMPL"]
        else:
            os.environ["REPRO_KERNEL_IMPL"] = prev
    return pre, np.asarray(lg[:len(rows), 0], np.float32)


def _greedy_gap(oracle, tok: int) -> float:
    """How far below the oracle's top logit ``tok``'s logit lies, in
    standard deviations of the oracle's logits (0 for its argmax)."""
    return float((oracle.max() - oracle[tok]) / oracle.std())


def served_tokens_phase(results, pre, dec) -> None:
    """Every closed-batch request's first two served tokens against the
    oracle's greedy choice, up to ``GREEDY_MARGIN`` for bf16 near-ties.
    At least one of them must have had its prefix restored from the
    constellation."""
    for i, r in enumerate(results):
        gaps = [_greedy_gap(pre[i], r.token_ids[0])]
        if len(r.token_ids) > 1:
            gaps.append(_greedy_gap(dec[i], r.token_ids[1]))
        ok = max(gaps) <= GREEDY_MARGIN
        _say("served", f"request {i} ({r.cached_tokens} tokens from the "
                       f"constellation): tokens {r.token_ids[:2]} oracle "
                       f"argmax {[int(pre[i].argmax()), int(dec[i].argmax())]}"
                       f" gaps {gaps!r} std (margin {GREEDY_MARGIN}) "
                       f"{'ok' if ok else 'FAIL'}")
        _check(ok, f"request {i}: served tokens are not the oracle's")
    _check(any(r.cached_tokens > 0 for r in results),
           "no checked request had a constellation hit")


def _rel(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def served_logits_phase(size: Size, cluster, tokens, first, pre_ref,
                        dec_ref) -> None:
    """One prompt served again by replica 0's own programs: Get KVC from
    the constellation, page import into a slot of the served pool, the
    tail through the engine's chunk-wave program at its chunk buffer,
    then the engine's decode step at ``max_batch``.  The prefill logits
    are the chunk wave's; the decode-step logits come from the model's
    paged decode step jitted at the same shapes over the same pool, whose
    greedy token the engine's step must also give."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.sampler import stack_sampling

    eng = cluster.engines[0]
    ex, pool, kv = eng.executor, eng.cache, eng.kv
    n, slot, b = len(tokens), 0, size.max_batch
    _check(pool.pages_allocated(slot) == 0, "replica 0's slot 0 is busy")
    payload, cached, ready_at = kv.lookup_prefix(tokens)
    _check(payload is not None and cached > 0,
           "logits: the prompt's prefix is not in the constellation")
    kv.wait_fetch(ready_at)
    k_blocks, v_blocks = kv.pages_async(payload, cached).result()
    pool.ensure_capacity(slot, n + 1)
    pool.write_pages(slot, 0, k_blocks, v_blocks)
    start = min(cached, n - 1)
    pre = _prefill_rows(ex.chunk_wave, pool, [slot], [tokens], [start],
                        ex.chunk_tokens, ex.chunk_buf)[0]

    toks = np.zeros(b, np.int32)
    lens = np.zeros(b, np.int32)
    toks[slot], lens[slot] = first, n
    bt = jnp.asarray(pool.block_tables)
    nxt = int(np.asarray(ex.step(bt, jnp.asarray(lens), jnp.asarray(toks),
                                 *stack_sampling([], pad_to=b),
                                 "greedy"))[slot])
    step = jax.jit(lambda *a: eng.model.decode_step_paged(*a),
                   donate_argnums=(1, 2))
    lg, pool.k_pool, pool.v_pool = step(
        ex.params, pool.k_pool, pool.v_pool, jnp.asarray(toks[:, None]), bt,
        jnp.asarray(lens))
    dec = np.asarray(lg[slot, 0], np.float32)
    pool.free_slot(slot)

    for name, got, want in (
            (f"prefill after restoring {cached} of {n} tokens, chunk-wave "
             f"program", pre, pre_ref),
            (f"first decode step at batch {b} over the served pool", dec,
             dec_ref)):
        rel = _rel(got, want)
        ok = bool(np.isfinite(got).all()) and rel <= LOGITS_TOL
        _say("logits", f"{name}: |served-oracle|/|oracle|={rel!r} "
                       f"max|diff|={float(np.abs(got - want).max())!r} "
                       f"argmax {int(got.argmax())} vs "
                       f"{int(want.argmax())} (tol {LOGITS_TOL}) "
                       f"{'ok' if ok else 'FAIL'}")
        _check(ok, f"served {name} logits disagree with the jnp oracle")
    gap = _greedy_gap(dec_ref, nxt)
    ok = gap <= GREEDY_MARGIN
    _say("logits", f"engine decode step token {nxt}, oracle argmax "
                   f"{int(dec_ref.argmax())}, gap {gap!r} std (margin "
                   f"{GREEDY_MARGIN}) {'ok' if ok else 'FAIL'}")
    _check(ok, "the engine's decode step disagrees with the oracle")


def answers_phase(size: Size, cluster, model, params, reqs,
                  results) -> None:
    from repro.serving.tokenizer import truncate_prompt

    eng = cluster.engines[0]
    rows = [truncate_prompt(eng.tokenizer.encode(r.prompt), size.max_seq_len)
            for r in reqs]
    first = [r.token_ids[0] for r in results]
    pre, dec = oracle_logits(size, model, params, rows, first,
                             eng.executor.chunk_tokens, eng.executor.chunk_buf)
    served_tokens_phase(results, pre, dec)
    h = next(i for i, r in enumerate(results) if r.cached_tokens > 0)
    served_logits_phase(size, cluster, rows[h], first[h], pre[h], dec[h])


# ---------------------------------------------------------------------------

def run(size: Size, device: dict, *, compiled: bool = True) -> None:
    """Every phase after the device check, then the ``ok`` line.
    ``compiled=False`` runs the kernels in interpret mode and skips the
    compiled-kernel check of the step programs (CPU rehearsal)."""
    import jax

    from repro.models.model import Model

    compile_s = [0.0]

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _say("device", json.dumps(device))

    kernels_phase(size, compiled=compiled)

    model = Model(size.cfg)
    params = model.init(jax.random.PRNGKey(size.seed))
    pages, free = _pages_per_replica(size, params)
    cluster = build_cluster(size, model, params, pages)
    pool_bytes = sum(e.cache.k_pool.nbytes + e.cache.v_pool.nbytes
                     for e in cluster.engines)
    if free is None:
        _say("closed", f"pools: {pages} pages/replica, {pool_bytes} bytes "
                       f"(the device reports no free HBM)")
    else:
        share = pool_bytes / free
        _say("closed", f"pools: {pages} pages/replica x {size.replicas}, "
                       f"{pool_bytes} bytes = {share!r} of the {free} "
                       f"bytes free after params")
        _check(share >= 0.5, "pools take less than half the free HBM")

    results, tokens, wall = closed_batch_phase(size, cluster)
    pct = cluster.merged_stats().latency_percentiles()
    answers_phase(size, cluster, model, params, closed_requests(size),
                  results)
    stream = stream_phase(size, cluster)
    if compiled:
        kernel_programs_phase(size, cluster)
    else:
        _say("programs", "not checked: kernels run in interpret mode")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")

    slo = stream.slo
    _say("info", "one run of chip_smoke.py, not a benchmark: "
                 f"compile_s={compile_s[0]!r} "
                 f"closed_batch tokens={tokens} wall_s={wall!r} "
                 f"tokens_per_s={tokens / wall!r} "
                 f"ttft_s p50={pct['ttft_s']['p50']!r} "
                 f"p99={pct['ttft_s']['p99']!r} "
                 f"itl_s p50={pct['itl_s']['p50']!r} "
                 f"p99={pct['itl_s']['p99']!r} "
                 f"stream tokens_per_s={slo['tokens_per_s']!r} "
                 f"peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main() -> int:
    try:
        from repro.jit_cache import enable_compile_cache

        enable_compile_cache()
        device = check_device()
        run(Size.full(), device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
