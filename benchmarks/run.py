"""Benchmark driver -- one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * fig1_2_isl_latency   -- intra-plane ISL latency vs (M, h)   (Figs 1-2)
  * table1_memory_tiers  -- memory-hierarchy placement of LEO    (Table 1)
  * fig16_strategy_sim   -- worst-case latency per strategy      (Fig 16)
  * table3_kvc_speedup   -- generation speedup from the KVC      (Table 3)
  * tpu_strategy_costs   -- chip-scale placement costs (beyond-paper)
  * protocol_micro       -- set/get/lookup microbenchmarks
  * serving_throughput   -- paged continuous-batching engine tokens/s vs
                            the pre-paged (seed) decode loop, plus the
                            chunked-admission scenario (mixed
                            prefill+decode: ITL p99 / decode tokens/s
                            while a long prompt admits, chunked scheduler
                            vs stop-the-world), the oversubscribed-pool
                            scenario (pool sized for half the live
                            sequences; preemption-by-offload must complete
                            every request at >= 0.8x full-pool tokens/s),
                            and the cluster_scale_out scenario (1/2/4
                            Engine replicas over ONE shared constellation
                            with experienced -- clocked -- Get KVC
                            latency; hop-aware prefix-affinity routing vs
                            the random baseline on aggregate tokens/s and
                            constellation hit rate), and the faulty_fabric
                            scenario (seeded satellite kills mid-serve:
                            k=2 chunk replication holds the prefix hit
                            rate that k=1 loses, all requests complete
                            with byte-identical outputs); also writes
                            BENCH_serving.json for trend tracking

Run: PYTHONPATH=src python -m benchmarks.run [--full | --smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

sys.path.insert(0, "src")

from repro.jit_cache import enable_compile_cache  # noqa: E402


def _time_us(fn, iters=3):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def fig1_2_isl_latency():
    from repro.core.simulator import intra_plane_latency_s, isl_latency_grid

    grid = isl_latency_grid()
    us = _time_us(lambda: isl_latency_grid())
    # derived: latency at the paper's extrapolation point (50 sats, 550 km)
    lat50 = intra_plane_latency_s(50, 550.0) * 1e3
    rows = [("fig1_2_isl_latency", us, f"lat(M=50,h=550km)={lat50:.2f}ms")]
    for m, h, lat in grid:
        if m in (15, 50, 100) and h in (550, 2000):
            rows.append((f"fig1_2[M={m},h={int(h)}km]", 0.0,
                         f"{lat*1e3:.3f}ms"))
    return rows


def table1_memory_tiers():
    from repro.core.simulator import (
        MEMORY_HIERARCHY_S,
        intra_plane_latency_s,
        memory_tier_for_latency,
        required_sats_per_plane_for,
    )

    lat = intra_plane_latency_s(60, 550.0)
    tier = memory_tier_for_latency(lat)
    m_needed = required_sats_per_plane_for(2e-3, 550.0)
    us = _time_us(lambda: memory_tier_for_latency(lat))
    return [
        ("table1_memory_tiers", us,
         f"one-hop(M=60)={lat*1e3:.2f}ms tier='{tier}' "
         f"M_for_2ms={m_needed} tiers={len(MEMORY_HIERARCHY_S)}"),
    ]


def fig16_strategy_sim():
    import dataclasses

    from repro.core.mapping import Strategy
    from repro.core.simulator import SimConfig, sweep, worst_case_latency

    us = _time_us(lambda: sweep(), iters=1)
    rows = [("fig16_strategy_sim", us, "sweep=3x4x4")]
    for s in (9, 81):
        per = {}
        for strat in Strategy:
            cfg = dataclasses.replace(SimConfig(), num_servers=s,
                                      altitude_km=550.0)
            per[strat.value] = worst_case_latency(strat, cfg).worst_latency_s
        rows.append((f"fig16[servers={s},h=550]", 0.0,
                     " ".join(f"{k}={v*1e3:.1f}ms" for k, v in per.items())))
    lo = worst_case_latency(
        Strategy.ROTATION_HOP,
        dataclasses.replace(SimConfig(), num_servers=9))
    hi = worst_case_latency(
        Strategy.ROTATION_HOP,
        dataclasses.replace(SimConfig(), num_servers=81))
    red = (1 - hi.worst_latency_s / lo.worst_latency_s) * 100
    rows.append(("fig16[9->81 servers]", 0.0,
                 f"latency_reduction={red:.1f}% (paper: ~90%)"))
    return rows


def table3_kvc_speedup(quick: bool = True):
    """Paper §5: generation with vs without the SkyMemory KVC.

    The paper's testbed (TinyLlama-1.1B on a Jetson + 19x5 cFS
    constellation) measured 21-24% end-to-end speedup for a ~250-char
    context prompt.  Same protocol in-process: TinyLlama-family model
    (reduced depth in quick mode so the benchmark stays CPU-friendly),
    128-token blocks, 6 kB chunks, 10 LOS servers.
    """
    import jax

    from repro.configs import get_config
    from repro.core import (
        ConstellationKVC, ConstellationSpec, LosWindow, Sat, Strategy,
    )
    from repro.models.model import Model
    from repro.serving import Engine, Request, SamplingParams

    cfg = get_config("skymemory-tinyllama")
    if quick:
        # reduced depth + f32 (CPU-native) so outputs are bit-comparable
        cfg = cfg.replace(num_layers=4, d_model=512, num_heads=8,
                          num_kv_heads=4, head_dim=64, d_ff=1408,
                          dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = ConstellationSpec(num_planes=5, sats_per_plane=19,
                             altitude_km=550.0)  # the paper's 19x5 testbed
    kvc = ConstellationKVC(
        spec, LosWindow(Sat(2, 9), 5, 5), Strategy.ROTATION_HOP,
        num_servers=10, chunk_bytes=6 * 1024,
    )
    prompt = ("SkyMemory expands cache memory to LEO constellations, "
              "highly distributed systems with thousands of satellites "
              "connected by free-space optics, always one hop from any "
              "point on earth. This context repeats in RAG workloads. ") * 8
    sp = SamplingParams(max_new_tokens=30)

    # each path runs twice; the second run is timed (steady-state graphs,
    # as on the paper's testbed where the model is long-resident)
    eng_cold = Engine(model, params, kvc=None, max_seq_len=1024)
    eng_cold.generate([Request(prompt=prompt, sampling=sp)])
    t0 = time.perf_counter()
    r_cold = eng_cold.generate([Request(prompt=prompt, sampling=sp)])[0]
    t_cold = time.perf_counter() - t0

    eng_warm = Engine(model, params, kvc=kvc, block_size=128,
                      max_seq_len=1024, write_back=True)
    eng_warm.generate([Request(prompt=prompt, sampling=sp)])  # warm cache
    eng_warm.write_back = False
    eng_warm.generate([Request(prompt=prompt, sampling=sp)])  # warm graphs
    t0 = time.perf_counter()
    r_warm = eng_warm.generate([Request(prompt=prompt, sampling=sp)])[0]
    t_warm = time.perf_counter() - t0

    speedup = (1 - t_warm / t_cold) * 100
    # token-level agreement: identical up to float reduction-order ties
    # (the cached path evaluates a 1-row attention graph, the cold path a
    # full-prefill graph; a near-tie may flip one greedy token after which
    # sequences diverge -- tests/test_serving.py checks strict identity on
    # controlled cases)
    pairs = list(zip(r_cold.token_ids, r_warm.token_ids))
    div = next((i for i, (a, b) in enumerate(pairs) if a != b), len(pairs))
    return [(
        "table3_kvc_speedup", t_cold * 1e6,
        f"no_kvc={t_cold:.2f}s kvc={t_warm:.2f}s speedup={speedup:.0f}% "
        f"cached_tokens={r_warm.cached_tokens} "
        f"tokens_identical_until={div}/{len(pairs)} (paper: 21-24%)",
    )]


def _seed_sample(logits, key, sp):
    """Verbatim replica of the seed engine's per-request sampler (argmax
    short-circuit for greedy) so the baseline is not penalized by the new
    vectorized sampler's machinery."""
    import jax
    import jax.numpy as jnp

    if sp.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / sp.temperature
    if sp.top_k:
        kth = jax.lax.top_k(logits, sp.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if sp.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(csum < sp.top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _seed_style_tokens_per_s(model, params, requests, batch, max_seq_len,
                             decode=None):
    """The pre-paged-runtime serving loop, kept here as the historical
    baseline: static batches of ``batch`` requests, one-at-a-time dense
    prefill, per-layer ``.at[].set`` restacking into a dense batch cache,
    and a per-sequence Python sampling loop with one ``int(...)`` host
    sync per sequence per token.  A batch runs until its *slowest* member
    finishes (finished slots idle) -- the utilization gap continuous
    batching closes.

    ``decode`` must be the caller's long-lived ``jax.jit(model.
    decode_step)``: the seed engine jitted once in __init__, and a fresh
    jit wrapper per call would charge retrace/compile to the timed
    window (jit of a bound method does not share the trace cache).
    """
    import jax
    import jax.numpy as jnp

    from repro.serving.tokenizer import ByteTokenizer

    cfg = model.cfg
    tok = ByteTokenizer(cfg.vocab_size)
    if decode is None:
        decode = jax.jit(model.decode_step)
    key = jax.random.PRNGKey(0)
    produced = 0

    t_start = time.perf_counter()
    for lo in range(0, len(requests), batch):
        chunk = requests[lo : lo + batch]
        seq_tokens, states, last_logits = [], [], []
        for r in chunk:
            ids = tok.encode(r.prompt)[: max_seq_len - 64]
            lg, _, st = model.forward(
                params, jnp.asarray(ids, jnp.int32)[None], collect_state=True)
            seq_tokens.append(ids)
            states.append(st)
            last_logits.append(lg[0, -1])
        b = len(chunk)
        cache = model.init_cache(b, max_seq_len)
        for i, st in enumerate(states):
            n = len(seq_tokens[i])
            cache["kv"]["k"] = cache["kv"]["k"].at[:, i, :n].set(
                st["kv"]["k"][:, 0, :n])
            cache["kv"]["v"] = cache["kv"]["v"].at[:, i, :n].set(
                st["kv"]["v"][:, 0, :n])
        pos = jnp.asarray([len(t) for t in seq_tokens], jnp.int32)
        logits = jnp.stack(last_logits)
        done = [False] * b
        out_len = [0] * b
        max_new = max(r.sampling.max_new_tokens for r in chunk)
        for _ in range(max_new):
            key, k = jax.random.split(key)
            keys = jax.random.split(k, b)
            nxt = jnp.stack(
                [_seed_sample(logits[i : i + 1], keys[i],
                              chunk[i].sampling)[0] for i in range(b)])
            for i in range(b):
                if done[i]:
                    continue
                tid = int(nxt[i])     # per-sequence host sync (seed behavior)
                out_len[i] += 1
                produced += 1
                if (tid == tok.eos_id
                        or out_len[i] >= chunk[i].sampling.max_new_tokens):
                    done[i] = True
            if all(done):
                break
            lg, cache = decode(params, cache, nxt[:, None], pos)
            logits = lg[:, 0]
            pos = pos + 1
    wall = time.perf_counter() - t_start
    return produced / wall, wall


def serving_throughput(quick: bool = True, smoke: bool = False,
                       json_path: str | None = "BENCH_serving.json"):
    """Paged continuous-batching engine tokens/s at batch 1/4/8, with and
    without SkyMemory prefix hits, vs the seed-style decode loop."""
    import jax

    from repro.configs import get_config
    from repro.core import (
        ConstellationKVC, ConstellationSpec, LosWindow, Sat, Strategy,
    )
    from repro.models.model import Model
    from repro.serving import Engine, EngineStats, Request, SamplingParams

    cfg = get_config("skymemory-tinyllama")
    if smoke:
        cfg = cfg.replace(num_layers=2, d_model=256, num_heads=4,
                          num_kv_heads=2, head_dim=64, d_ff=512,
                          vocab_size=512, dtype="float32")
    elif quick:
        cfg = cfg.replace(num_layers=4, d_model=512, num_heads=8,
                          num_kv_heads=4, head_dim=64, d_ff=1408,
                          dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # decode-heavy, heterogeneous stream: generation lengths spread 8..128
    # (chat-style outputs), ~230-token prompts -- the regime a serving
    # engine lives in
    gen_lens = (2, 4, 8, 16) if smoke else (8, 16, 32, 128)
    max_seq_len = 512
    block = 128
    base = ("SkyMemory expands cache memory to LEO constellations, one "
            "hop from any point on earth; this context repeats in RAG "
            "workloads and fills a few cache blocks. ")

    def reqs(b):
        """A serving stream: 2x the slot count with a spread of generation
        lengths (real request streams are heterogeneous -- that is the
        regime continuous batching exists for).  Static batching idles
        finished slots until the slowest member of each chunk completes;
        continuous batching backfills them from the queue."""
        return [
            Request(prompt=f"{base} request {i}",
                    sampling=SamplingParams(
                        max_new_tokens=gen_lens[i % len(gen_lens)]))
            for i in range(2 * b)
        ]

    rows, record = [], {"config": cfg.name, "smoke": smoke,
                        "max_new_tokens": list(gen_lens),
                        "requests_per_run": "2x batch", "batches": {}}
    for b in (1, 4, 8):
        # best-of-3 timed runs throughout: host interference (shared CPU)
        # only ever slows a run down, so the best run is the real rate
        eng = Engine(model, params, kvc=None, max_seq_len=max_seq_len,
                     max_batch=b)
        eng.generate(reqs(b))                      # warm compiles
        best = None                                # (tps, wall, dec, stats)
        for _ in range(3):
            eng.stats = EngineStats()
            t0 = time.perf_counter()
            out = eng.generate(reqs(b))
            run_wall = time.perf_counter() - t0
            toks = sum(len(r.token_ids) for r in out)
            run = (toks / run_wall, run_wall,
                   (eng.stats.decoded_tokens - eng.stats.requests)
                   / max(eng.stats.decode_time_s, 1e-9), eng.stats)
            if best is None or run[0] > best[0]:
                best = run                         # all fields from the
        tps, wall, dec_tps, stats = best           # same (best) run

        # warm SkyMemory prefix: same prompts again hit full blocks
        kvc = ConstellationKVC(
            ConstellationSpec(5, 19, 550.0), LosWindow(Sat(2, 9), 5, 5),
            Strategy.ROTATION_HOP, num_servers=10, chunk_bytes=6 * 1024,
        )
        eng_c = Engine(model, params, kvc=kvc, block_size=block,
                       max_seq_len=max_seq_len, max_batch=b)
        eng_c.generate(reqs(b))                    # cold: populate + compile
        eng_c.write_back = False
        tps_hit, cached = 0.0, 0
        for _ in range(2):
            t0 = time.perf_counter()
            out_c = eng_c.generate(reqs(b))
            wall_c = time.perf_counter() - t0
            toks_c = sum(len(r.token_ids) for r in out_c)
            tps_hit = max(tps_hit, toks_c / wall_c)
            cached = sum(r.cached_tokens for r in out_c)

        seed_decode = jax.jit(model.decode_step)     # seed jitted once
        _seed_style_tokens_per_s(model, params, reqs(b), b, max_seq_len,
                                 decode=seed_decode)  # warm seed compiles
        seed_tps = max(
            _seed_style_tokens_per_s(model, params, reqs(b), b,
                                     max_seq_len, decode=seed_decode)[0]
            for _ in range(3))
        speedup = tps / seed_tps
        rows.append((
            f"serving_throughput[batch={b}]", wall * 1e6,
            f"tok/s={tps:.1f} decode_tok/s={dec_tps:.1f} "
            f"tok/s_prefix_hit={tps_hit:.1f} cached={cached} "
            f"seed_tok/s={seed_tps:.1f} speedup_vs_seed={speedup:.2f}x",
        ))
        record["batches"][str(b)] = {
            "tokens_per_s": tps,
            "decode_tokens_per_s": dec_tps,
            "tokens_per_s_prefix_hit": tps_hit,
            "cached_tokens_prefix_hit": cached,
            "seed_engine_tokens_per_s": seed_tps,
            "speedup_vs_seed": speedup,
            "decode_steps": stats.decode_steps,
            "mid_decode_admissions": stats.mid_decode_admissions,
            "prefill_chunks": stats.prefill_chunks,
            "latency_percentiles": stats.latency_percentiles(),
        }

    # run each scenario behind a cache clear: dropping the executables
    # releases their JIT code mappings (a long single process otherwise
    # accumulates enough to exhaust vm.max_map_count and abort inside
    # LLVM), and the persistent compilation cache (enable_compile_cache)
    # turns any recompile into a cheap deserialize
    scenarios = [
        ("chunked_admission", _chunked_admission),
        ("oversubscribed_pool", _oversubscribed_pool),
        ("cluster_scale_out", _cluster_scale_out),
        ("faulty_fabric", _faulty_fabric),
        ("degraded_fabric", _degraded_fabric),
        ("striped_directory", _striped_directory),
        ("quantized_payloads", _quantized_payloads),
        ("sustained_load", _sustained_load),
        ("chaos_sustained_load", _chaos_sustained_load),
    ]
    for key, fn in scenarios:
        jax.clear_caches()
        sc_rows, sc_record = fn(model, params, smoke=smoke)
        rows.extend(sc_rows)
        record[key] = sc_record
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        rows.append(("serving_throughput[json]", 0.0, json_path))
    # enforce the scale-out bars AFTER the record is written, so a
    # failing run still uploads the evidence: affinity routing must meet
    # or beat random tokens/s and strictly beat its hit rate at >= 2
    # replicas, with nonzero experienced L2 wait
    acc = record["cluster_scale_out"]["acceptance"]
    if not all(acc.values()):
        raise SystemExit(f"cluster_scale_out acceptance failed: {acc}")
    facc = record["faulty_fabric"]["acceptance"]
    if not all(facc.values()):
        raise SystemExit(f"faulty_fabric acceptance failed: {facc}")
    dacc = record["degraded_fabric"]["acceptance"]
    if not all(dacc.values()):
        raise SystemExit(f"degraded_fabric acceptance failed: {dacc}")
    sacc = record["striped_directory"]["acceptance"]
    if not all(sacc.values()):
        raise SystemExit(f"striped_directory acceptance failed: {sacc}")
    qacc = record["quantized_payloads"]["acceptance"]
    if not all(qacc.values()):
        raise SystemExit(f"quantized_payloads acceptance failed: {qacc}")
    uacc = record["sustained_load"]["acceptance"]
    if not all(uacc.values()):
        raise SystemExit(f"sustained_load acceptance failed: {uacc}")
    cacc = record["chaos_sustained_load"]["acceptance"]
    if not all(cacc.values()):
        raise SystemExit(f"chaos_sustained_load acceptance failed: {cacc}")
    return rows


def _chunked_admission(model, params, *, smoke: bool):
    """Mixed prefill+decode: a long-prompt request admits into a live
    decode batch.  Compares the chunked-prefill scheduler (prompt chunks
    ride the decode step) against stop-the-world admission
    (``chunk_tokens=0``, the pre-chunked baseline) on the two SLOs the
    scheduler exists for: p99 inter-token latency of the *running*
    sequences while the admission is in flight, and decode throughput
    over the same window (chunking must smooth the tail without giving
    back tokens/s)."""
    from repro.serving import Engine, EngineStats, Request, SamplingParams

    b = 4
    max_seq_len = 512
    gen_long = 24 if smoke else 96
    base = "SkyMemory keeps decode hot while long prompts admit. "
    long_prompt = base * 9          # ~440 tokens: several 128-token chunks

    def reqs():
        # slot 0 finishes early, freeing a slot mid-decode; the queued
        # long-prompt request then admits while 3 sequences still decode
        out = [Request(prompt=f"{base} warm {i}",
                       sampling=SamplingParams(
                           max_new_tokens=8 if i == 0 else gen_long))
               for i in range(b)]
        out.append(Request(prompt=long_prompt,
                           sampling=SamplingParams(max_new_tokens=8)))
        return out

    # one page (= one SkyMemory block) per chunk: the finest page-aligned
    # budget, so admission work interleaves with decode at block grain
    engines = {"chunked": 128, "stop_the_world": 0}
    results: dict[str, dict] = {}
    for name, chunk_tokens in engines.items():
        engines[name] = Engine(model, params, max_seq_len=max_seq_len,
                               max_batch=b, chunk_tokens=chunk_tokens)
        engines[name].generate(reqs())         # warm compiles
    # repetitions are interleaved A,B,A,B so slow host drift hits both
    # engines alike; per metric the best rep is kept (shared-CPU noise
    # only ever slows a run down)
    for _ in range(3):
        for name, eng in engines.items():
            eng.stats = EngineStats()
            t0 = time.perf_counter()
            out = eng.generate(reqs())
            wall = time.perf_counter() - t0
            pct = eng.stats.latency_percentiles()
            run = {
                "decode_tokens_per_s": eng.stats.decoded_tokens / wall,
                "itl_p50_s": pct["itl_s"]["p50"],
                "itl_p99_s": pct["itl_s"]["p99"],
                # ITL seen by running sequences while the admission was
                # in flight: the stall the chunked scheduler removes
                "itl_admission_p99_s": pct["itl_admission_s"]["p99"],
                "ttft_long_s": out[-1].ttft_s,
                "prefill_chunks": eng.stats.prefill_chunks,
                "mid_decode_admissions": eng.stats.mid_decode_admissions,
            }
            best = results.get(name)
            if best is None:
                results[name] = run
            else:
                for key in ("itl_p50_s", "itl_p99_s",
                            "itl_admission_p99_s", "ttft_long_s"):
                    best[key] = min(best[key], run[key])
                best["decode_tokens_per_s"] = max(
                    best["decode_tokens_per_s"], run["decode_tokens_per_s"])

    imp = results["stop_the_world"]["itl_admission_p99_s"] / max(
        results["chunked"]["itl_admission_p99_s"], 1e-9)
    ratio = (results["chunked"]["decode_tokens_per_s"]
             / max(results["stop_the_world"]["decode_tokens_per_s"], 1e-9))
    record = {
        "long_prompt_chars": len(long_prompt),
        "running_decodes_during_admission": b - 1,
        "itl_admission_p99_improvement_vs_stop_the_world": imp,
        "decode_tokens_per_s_ratio_vs_stop_the_world": ratio,
        **{k: v for k, v in results.items()},
    }
    rows = [(
        "chunked_admission", 0.0,
        "itl_admission_p99="
        f"{results['chunked']['itl_admission_p99_s']*1e3:.1f}ms vs "
        f"{results['stop_the_world']['itl_admission_p99_s']*1e3:.1f}ms "
        f"stop-world (improvement={imp:.1f}x) "
        f"decode_tok/s_ratio={ratio:.2f} "
        f"ttft_long={results['chunked']['ttft_long_s']*1e3:.0f}ms vs "
        f"{results['stop_the_world']['ttft_long_s']*1e3:.0f}ms",
    )]
    return rows, record


def _oversubscribed_pool(model, params, *, smoke: bool):
    """Preemption-by-offload under memory pressure: a free-list pool
    sized for HALF the live sequences' steady-state footprint serves a
    2x-batch request stream.  Sequences co-admit lazily (pages for the
    prompt + one decode write), grow page-by-page, and when the pool
    runs dry the scheduler offloads the lowest-priority victim to the
    host tier and restores it later -- so every request completes with
    zero admission refusals.  The score is tokens/s relative to the same
    stream on a full (contiguous) pool at the same batch: the acceptance
    bar is >= 0.8x."""
    from repro.serving import Engine, EngineStats, Request, SamplingParams

    b = 8
    max_seq_len = 512
    block = 128
    pages_per_seq = max_seq_len // block
    gen_lens = (24, 32, 48, 200)
    base = ("SkyMemory swaps cold sequences to the constellation under "
            "pool pressure and restores them through chunked prefill. ")

    def reqs():
        # a sustained heterogeneous stream: short requests churn through
        # the slots for the whole run while every 4th request decodes
        # long enough to grow into a 3rd page.  Long sequences accumulate
        # (each lives ~200 steps, one admits every few dozen), so live
        # page demand spends most of the run above the half pool's 16
        # pages -- growth pressure that forces real preemptions, not just
        # admission queueing
        return [
            Request(prompt=f"{base} oversubscribed request {i} " + "pad " * 26,
                    sampling=SamplingParams(
                        max_new_tokens=gen_lens[i % len(gen_lens)]))
            for i in range(4 * b)
        ]

    engines = {
        "full_pool": Engine(model, params, max_seq_len=max_seq_len,
                            max_batch=b),
        "half_pool": Engine(model, params, max_seq_len=max_seq_len,
                            max_batch=b,
                            num_pages=1 + b * pages_per_seq // 2),
    }
    results: dict[str, dict] = {}
    for eng in engines.values():
        eng.generate(reqs())                   # warm compiles
    # interleave repetitions so host drift hits both engines alike; keep
    # the best rep per engine (shared-CPU noise only slows runs down)
    for _ in range(3):
        for name, eng in engines.items():
            eng.stats = EngineStats()
            t0 = time.perf_counter()
            out = eng.generate(reqs())
            wall = time.perf_counter() - t0
            toks = sum(len(r.token_ids) for r in out)
            run = {
                "tokens_per_s": toks / wall,
                "requests_completed": sum(
                    1 for r in out if len(r.token_ids) > 0),
                "admission_refusals": len(reqs()) - len(out),
                "preemptions": eng.stats.preemptions,
                "restores": eng.stats.restores,
                "offloaded_pages": eng.stats.offloaded_pages,
                "replayed_tokens": eng.stats.replayed_tokens,
            }
            best = results.get(name)
            if best is None or run["tokens_per_s"] > best["tokens_per_s"]:
                results[name] = run

    ratio = (results["half_pool"]["tokens_per_s"]
             / max(results["full_pool"]["tokens_per_s"], 1e-9))
    record = {
        "batch": b,
        "requests": 4 * b,
        "half_pool_pages": 1 + b * pages_per_seq // 2,
        "full_pool_pages": b * pages_per_seq,
        "tokens_per_s_ratio_vs_full_pool": ratio,
        **results,
    }
    hp = results["half_pool"]
    rows = [(
        "oversubscribed_pool", 0.0,
        f"tok/s={hp['tokens_per_s']:.1f} vs "
        f"{results['full_pool']['tokens_per_s']:.1f} full-pool "
        f"(ratio={ratio:.2f}) preemptions={hp['preemptions']} "
        f"restores={hp['restores']} "
        f"completed={hp['requests_completed']}/{4 * b} "
        f"refusals={hp['admission_refusals']}",
    )]
    return rows, record


def _cluster_scale_out(model, params, *, smoke: bool):
    """Scale-out over one shared constellation: 1 vs 2 vs 4 Engine
    replicas serve a duplicated-prefix stream through a router, with the
    fabric's ``SimClock`` making Get KVC flights *experienced* (deferred
    fetches overlap decode steps; the un-hidden remainder is waited out
    and accounted).  At >= 2 replicas the hop-aware prefix-affinity
    policy is compared against seeded random routing on the two scale-out
    scores: aggregate tokens/s and the shared-constellation prefix hit
    rate.  Affinity keeps each duplicated group on one replica, so later
    members hit blocks the group head already wrote; random routing
    splits groups across concurrently-running replicas, whose lookups
    race the write-backs and miss."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, IslTransport, LosWindow, Sat,
        SimClock, Strategy,
    )
    from repro.serving import EngineCluster, Request, SamplingParams

    max_seq_len = 512
    block = 128
    groups = 6
    dup = 4
    gen_new = 4 if smoke else 8
    filler = ("SkyMemory anchors serving replicas at different satellites "
              "of one shared orbital cache and routes repeated contexts "
              "to the replica already holding their blocks. ")

    def stream(rep: int):
        # `groups` distinct contexts (distinct from their first block, so
        # each has its own affinity home), `dup` members each, arriving
        # in bursts -- the RAG regime where one document's requests land
        # together.  Burst members routed to ONE replica hit in order
        # (each lookup drains the previous member's write-back); burst
        # members sprayed across replicas run concurrently, race the
        # group head's write-back, and miss.  `rep` namespaces
        # repetitions so every rep is a cold run
        return [
            Request(prompt=f"[rep {rep} doc {i // dup}] " + filler * 2,
                    sampling=SamplingParams(max_new_tokens=gen_new))
            for i in range(groups * dup)
        ]

    def build(n_replicas: int, policy: str) -> EngineCluster:
        spec = ConstellationSpec(15, 15, 550.0)
        # rate 5: ISL flights compress 5x in wall time but stay far
        # longer than host-side scheduling gaps, so un-hidden flight
        # time is really experienced (l2_wait_s > 0)
        clock = SimClock(rate=5.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024,
            transport=IslTransport(spec, clock=clock,
                                   chunk_processing_time_s=2e-4),
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=n_replicas, policy=policy,
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4,
        )
        # warm every replica's compiles directly (routing would leave
        # some replicas cold), in a prompt namespace the measured stream
        # never matches
        for i, eng in enumerate(cluster.engines):
            eng.generate([Request(prompt=f"[warm {i}] " + filler,
                                  sampling=SamplingParams(max_new_tokens=2))])
        cluster.reset_stats()
        return cluster

    def measure(cluster: EngineCluster, rep: int) -> dict:
        reqs = stream(rep)
        t0 = time.perf_counter()
        out = cluster.serve(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.token_ids) for r in out)
        merged = cluster.merged_stats()
        fabric = cluster.fabric_stats()
        run = {
            "tokens_per_s": toks / wall,
            "wall_s": wall,
            "requests": len(out),
            "prefix_hit_rate": fabric["prefix_hit_rate"],
            "cached_tokens": merged.cached_tokens,
            "prefilled_tokens": merged.prefilled_tokens,
            "block_hits": fabric["block_hits"],
            "block_misses": fabric["block_misses"],
            "l2_wait_s": merged.l2_wait_s,
            "l2_fetch_waits": merged.l2_fetch_waits,
            "l2_deferred_chunks": merged.l2_deferred_chunks,
            "replica_requests": [e.stats.requests for e in cluster.engines],
            "latency_percentiles": merged.latency_percentiles(),
            "transport_latency_s": fabric["transport_latency_s"],
        }
        cluster.reset_stats()
        return run

    rows, record = [], {"groups": groups, "dup_per_group": dup,
                        "max_batch_per_replica": 4, "replicas": {}}
    reps = 2
    for n in (1, 2, 4):
        policies = ["prefix_affinity"] if n == 1 else ["prefix_affinity",
                                                       "random"]
        clusters = {p: build(n, p) for p in policies}
        best: dict[str, dict] = {}
        # repetitions interleaved across policies so host drift hits both
        # alike; best aggregate tokens/s per policy is kept (shared-CPU
        # noise only ever slows a run down)
        for rep in range(reps):
            for p, cluster in clusters.items():
                run = measure(cluster, rep)
                if p not in best or run["tokens_per_s"] > best[p]["tokens_per_s"]:
                    best[p] = run
        entry = dict(best)
        aff = best["prefix_affinity"]
        if "random" in best:
            rnd = best["random"]
            entry["affinity_vs_random_tokens_per_s_ratio"] = (
                aff["tokens_per_s"] / max(rnd["tokens_per_s"], 1e-9))
            entry["affinity_hit_rate_minus_random"] = (
                aff["prefix_hit_rate"] - rnd["prefix_hit_rate"])
            rows.append((
                f"cluster_scale_out[replicas={n}]", 0.0,
                f"affinity tok/s={aff['tokens_per_s']:.1f} "
                f"hit={aff['prefix_hit_rate']*100:.0f}% vs random "
                f"tok/s={rnd['tokens_per_s']:.1f} "
                f"hit={rnd['prefix_hit_rate']*100:.0f}% "
                f"(ratio={entry['affinity_vs_random_tokens_per_s_ratio']:.2f}) "
                f"l2_wait={aff['l2_wait_s']*1e3:.0f}ms/"
                f"{aff['l2_fetch_waits']}waits",
            ))
        else:
            rows.append((
                f"cluster_scale_out[replicas={n}]", 0.0,
                f"tok/s={aff['tokens_per_s']:.1f} "
                f"hit={aff['prefix_hit_rate']*100:.0f}% "
                f"l2_wait={aff['l2_wait_s']*1e3:.0f}ms/"
                f"{aff['l2_fetch_waits']}waits",
            ))
        record["replicas"][str(n)] = entry

    multi = [record["replicas"][str(n)] for n in (2, 4)]
    record["acceptance"] = {
        "affinity_tokens_per_s_ge_random_at_2plus": all(
            e["affinity_vs_random_tokens_per_s_ratio"] >= 1.0
            for e in multi),
        "affinity_hit_rate_strictly_higher_at_2plus": all(
            e["affinity_hit_rate_minus_random"] > 0.0 for e in multi),
        "l2_fetch_latency_experienced": all(
            record["replicas"][str(n)]["prefix_affinity"]["l2_wait_s"] > 0.0
            for n in (1, 2, 4)),
    }
    rows.append(("cluster_scale_out[acceptance]", 0.0,
                 " ".join(f"{k}={v}" for k, v in record["acceptance"].items())))
    return rows, record


def _faulty_fabric(model, params, *, smoke: bool):
    """Fault-tolerant fabric: the PR-4 bursty duplicated-prefix stream
    served by a 2-replica cluster over a warmed, clocked constellation
    while a seeded ``FaultInjector`` kills chunk-server satellites with
    requests in flight.  Every block stripes over every chunk server, so
    with k=1 replication any kill zaps every cached block and the prefix
    hit rate collapses; with k=2 (plane-diverse replica homes chosen so
    the kill schedule never completes a home pair) degraded reads fall
    through the dead replicas and the hit rate must hold >= 80% of the
    unfaulted baseline.  Either way every request completes with tokens
    byte-identical to the fault-free run -- churn costs hit rate and
    latency, never answers.  After the serve, outstanding heals drain
    and a repair pass re-replicates what the crashes orphaned."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, FaultInjector, FaultPlan,
        IslTransport, LosWindow, Sat, SimClock, Strategy,
        plan_survivable_kills,
    )
    from repro.serving import EngineCluster, Request, SamplingParams

    max_seq_len = 512
    block = 128
    groups = 5
    dup = 4
    n_kills = 3
    gen_new = 4 if smoke else 8
    filler = ("SkyMemory replicates every KVC chunk across plane-diverse "
              "satellites so the orbital cache keeps answering while the "
              "constellation churns underneath the serving cluster. ")

    def stream(rep: int):
        # the cluster_scale_out burst shape: `groups` distinct contexts,
        # `dup` members each, arriving in bursts; `rep` namespaces the
        # warm pass away from the measured pass
        return [
            Request(prompt=f"[ff rep {rep} doc {i // dup}] " + filler * 2,
                    sampling=SamplingParams(max_new_tokens=gen_new))
            for i in range(groups * dup)
        ]

    def build(k: int):
        spec = ConstellationSpec(15, 15, 550.0)
        clock = SimClock(rate=5.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=k,
            transport=IslTransport(spec, clock=clock,
                                   chunk_processing_time_s=2e-4),
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=2, policy="prefix_affinity",
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4,
        )
        for i, eng in enumerate(cluster.engines):   # warm compiles
            eng.generate([Request(prompt=f"[ff warm {i}] " + filler,
                                  sampling=SamplingParams(max_new_tokens=2))])
        # warm the orbital cache: the measured pass serves a hot fabric
        cluster.serve(stream(0))
        cluster.reset_stats()
        return cluster, kvc

    def measure(k: int, faulted: bool) -> dict:
        cluster, kvc = build(k)
        inj = None
        if faulted:
            # the same seed (and identical server maps) gives k=1 and
            # k=2 the same kill schedule; survivability is computed at
            # k=2 geometry so k=2 is *meant* to survive it and k=1 to
            # collapse (every block stripes over every server)
            probe = kvc if k > 1 else build_probe()
            plan = FaultPlan.outages(
                plan_survivable_kills(probe, n_kills, seed=5),
                kill_at_s=0.0, stagger_s=0.1, downtime_s=1e9)
            inj = FaultInjector(kvc, plan)
            inj.arm()
        t0 = time.perf_counter()
        out = cluster.serve(stream(1))
        wall = time.perf_counter() - t0
        merged = cluster.merged_stats()
        fabric = cluster.fabric_stats()
        run = {
            "tokens_per_s": sum(len(r.token_ids) for r in out) / wall,
            "requests": len(out),
            "completed": sum(1 for r in out if len(r.token_ids) > 0),
            "prefix_hit_rate": fabric["prefix_hit_rate"],
            "cached_tokens": merged.cached_tokens,
            "degraded_reads": fabric["degraded_reads"],
            "lost_blocks": fabric["lost_blocks"],
            "engine_lost_block_lookups": merged.lost_blocks,
            "l2_wait_s": merged.l2_wait_s,
            "token_ids": [list(r.token_ids) for r in out],
        }
        if inj is not None:
            run["sat_kills"] = inj.stats.sat_kills
            run["chunks_dropped"] = inj.stats.chunks_dropped
            inj.drain()                      # outstanding heals land
            run["repaired_chunks"] = kvc.repair()
        return run

    def build_probe():
        # a throwaway k=2 store with the same geometry, to derive the
        # shared kill schedule for the k=1 run
        spec = ConstellationSpec(15, 15, 550.0)
        return ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=2,
        )

    baseline = measure(2, faulted=False)
    faulted = {k: measure(k, faulted=True) for k in (2, 1)}

    base_hit = baseline["prefix_hit_rate"]
    k2, k1 = faulted[2], faulted[1]
    n_reqs = groups * dup
    identical = all(
        run["token_ids"] == baseline["token_ids"] for run in (k2, k1))
    acceptance = {
        "k2_holds_80pct_of_unfaulted_hit_rate":
            k2["prefix_hit_rate"] >= 0.8 * base_hit,
        "k1_hit_rate_collapses":
            k1["prefix_hit_rate"] < 0.8 * base_hit
            and k1["prefix_hit_rate"] < k2["prefix_hit_rate"],
        "all_requests_complete": all(
            run["completed"] == n_reqs
            for run in (baseline, k2, k1)),
        "outputs_byte_identical_to_fault_free": identical,
        "degraded_reads_nonzero": k2["degraded_reads"] > 0,
        "repaired_chunks_nonzero": k2["repaired_chunks"] > 0,
    }
    record = {
        "groups": groups, "dup_per_group": dup, "replicas": 2,
        "sat_kills": n_kills,
        "unfaulted_prefix_hit_rate": base_hit,
        "unfaulted": {k: v for k, v in baseline.items()
                      if k != "token_ids"},
        "faulted_k2": {k: v for k, v in k2.items() if k != "token_ids"},
        "faulted_k1": {k: v for k, v in k1.items() if k != "token_ids"},
        "acceptance": acceptance,
    }
    rows = [(
        "faulty_fabric", 0.0,
        f"unfaulted hit={base_hit*100:.0f}% | k=2 under {n_kills} kills: "
        f"hit={k2['prefix_hit_rate']*100:.0f}% "
        f"degraded={k2['degraded_reads']} repaired={k2['repaired_chunks']} "
        f"| k=1: hit={k1['prefix_hit_rate']*100:.0f}% "
        f"lost={k1['engine_lost_block_lookups']} | "
        f"complete={k2['completed']}+{k1['completed']}/{2*n_reqs} "
        f"identical={identical}",
    ), (
        "faulty_fabric[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def _degraded_fabric(model, params, *, smoke: bool):
    """Graceful degradation end-to-end: the faulty-fabric stream over a
    k=2 cluster whose kill schedule deliberately COMPLETES a replica
    home pair (PR-5's unrecoverable loss) while three of the four ISLs
    around another chunk server stay severed for the whole run.  With a
    ``GroundStationTier`` attached (write-through) every chunk op still
    completes -- link outages grade into rerouted detours, orbital
    losses fall through to ground -- nothing is purged, and the end-of-
    run repair re-replicates the lost blocks from ground instead of
    counting them lost.  The same schedule without a ground tier
    degrades further: blocks purge, prefixes recompute, hit rate drops.
    Every request completes with tokens byte-identical to the fault-free
    run in all three scenarios -- degradation costs latency and hit
    rate, never answers."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, FaultInjector, FaultPlan,
        GroundStationTier, IslTransport, LosWindow, Sat, SimClock,
        Strategy,
    )
    from repro.core.faults import FaultEvent
    from repro.serving import EngineCluster, Request, SamplingParams

    max_seq_len = 512
    block = 128
    groups = 5
    dup = 4
    gen_new = 4 if smoke else 8
    filler = ("SkyMemory grades degradation instead of failing: dead ISL "
              "links reroute into detours, dead satellites fall through "
              "to the durable ground tier, and repair promotes the lost "
              "blocks back into orbit when their homes heal. ")
    spec = ConstellationSpec(15, 15, 550.0)

    def stream(rep: int):
        return [
            Request(prompt=f"[df rep {rep} doc {i // dup}] " + filler * 2,
                    sampling=SamplingParams(max_new_tokens=gen_new))
            for i in range(groups * dup)
        ]

    def build(with_ground: bool):
        clock = SimClock(rate=5.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=2,
            transport=IslTransport(spec, clock=clock,
                                   chunk_processing_time_s=2e-4,
                                   probe_timeout_s=5e-3),
            ground=(GroundStationTier(spec, processing_time_s=1e-3)
                    if with_ground else None),
            ground_write="all" if with_ground else "none",
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=2, policy="prefix_affinity",
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4,
        )
        for i, eng in enumerate(cluster.engines):   # warm compiles
            eng.generate([Request(prompt=f"[df warm {i}] " + filler,
                                  sampling=SamplingParams(max_new_tokens=2))])
        cluster.serve(stream(0))    # warm the orbital cache (and ground)
        cluster.reset_stats()
        return cluster, kvc

    def fault_plan(kvc) -> FaultPlan:
        events = []
        # >= 2 satellite kills that COMPLETE server 3's replica home
        # pair: chunk 3 of every cached block loses its last orbital
        # copy -- PR-5's unrecoverable loss, staged deliberately and
        # sustained for the whole serve.  The heal events land at the
        # end-of-run drain (wiped homes come back alive), giving the
        # final repair pass live destinations to re-replicate onto.
        for i, sat in enumerate(
                kvc.replica_sat(3, r) for r in range(2)):
            events.append(FaultEvent(at_s=i * 0.1, action="kill", sat=sat))
            events.append(FaultEvent(at_s=1e9, action="heal", sat=sat))
        # sustained link outages: sever three of the four ISLs around
        # two other chunk servers' homes for the whole run -- every op
        # touching them must detour (never fail; one live link remains)
        for hub in (kvc.replica_sat(5, 0), kvc.replica_sat(8, 0)):
            for dp, ds in ((1, 0), (-1, 0), (0, 1)):
                nb = spec.wrap(Sat(hub.plane + dp, hub.slot + ds))
                events.append(
                    FaultEvent(at_s=0.0, action="kill", link=(hub, nb)))
        return FaultPlan(events)

    def measure(with_ground: bool, faulted: bool) -> dict:
        cluster, kvc = build(with_ground)
        inj = None
        if faulted:
            inj = FaultInjector(kvc, fault_plan(kvc))
            inj.arm()
        t0 = time.perf_counter()
        out = cluster.serve(stream(1))
        wall = time.perf_counter() - t0
        merged = cluster.merged_stats()
        run = {
            "tokens_per_s": sum(len(r.token_ids) for r in out) / wall,
            "requests": len(out),
            "completed": sum(1 for r in out if len(r.token_ids) > 0),
            "cached_tokens": merged.cached_tokens,
            "engine_lost_block_lookups": merged.lost_blocks,
            "l2_wait_s": merged.l2_wait_s,
            "token_ids": [list(r.token_ids) for r in out],
        }
        if inj is not None:
            run["sat_kills"] = inj.stats.sat_kills
            run["link_kills"] = inj.stats.link_kills
            inj.drain()                      # outstanding heals land
            run["repaired_chunks"] = kvc.repair()
        # fabric counters AFTER repair: purge-at-loss and repair-from-
        # ground land on the base store, data-plane hits on the views
        fabric = cluster.fabric_stats()
        run.update({
            "prefix_hit_rate": fabric["prefix_hit_rate"],
            "degraded_reads": fabric["degraded_reads"],
            "detoured_ops": fabric["detoured_ops"],
            "detour_hops": fabric["detour_hops"],
            "ground_hits": fabric["ground_hits"],
            "lost_blocks": fabric["lost_blocks"],
            "repaired_from_ground": fabric["repaired_from_ground"],
        })
        return run

    baseline = measure(with_ground=True, faulted=False)
    grounded = measure(with_ground=True, faulted=True)
    bare = measure(with_ground=False, faulted=True)

    base_hit = baseline["prefix_hit_rate"]
    n_reqs = groups * dup
    identical = all(run["token_ids"] == baseline["token_ids"]
                    for run in (grounded, bare))
    acceptance = {
        # graceful, not cliff-shaped: every op completed via detour or
        # ground -- nothing failed, nothing purged, nothing recomputed
        "zero_failed_chunk_ops_with_ground":
            grounded["lost_blocks"] == 0
            and grounded["engine_lost_block_lookups"] == 0,
        "all_requests_complete": all(
            run["completed"] == n_reqs
            for run in (baseline, grounded, bare)),
        "link_outages_detour_not_fail":
            grounded["detoured_ops"] > 0 and bare["detoured_ops"] > 0,
        "ground_serves_orbital_losses": grounded["ground_hits"] > 0,
        # >= 90% of PR-5's lost blocks become repaired_from_ground
        "lost_blocks_become_repaired_from_ground":
            bare["lost_blocks"] > 0
            and grounded["repaired_from_ground"]
            >= 0.9 * bare["lost_blocks"],
        "hit_rate_holds_70pct_with_ground":
            grounded["prefix_hit_rate"] >= 0.7 * base_hit,
        "no_ground_degrades_further":
            bare["prefix_hit_rate"] < grounded["prefix_hit_rate"],
        "outputs_byte_identical_to_fault_free": identical,
    }
    record = {
        "groups": groups, "dup_per_group": dup, "replicas": 2,
        "replication": 2, "sat_kills": 2, "link_kills": 6,
        "unfaulted_prefix_hit_rate": base_hit,
        "unfaulted": {k: v for k, v in baseline.items()
                      if k != "token_ids"},
        "faulted_ground": {k: v for k, v in grounded.items()
                           if k != "token_ids"},
        "faulted_no_ground": {k: v for k, v in bare.items()
                              if k != "token_ids"},
        "acceptance": acceptance,
    }
    rows = [(
        "degraded_fabric", 0.0,
        f"unfaulted hit={base_hit*100:.0f}% | ground under 2 kills + 6 "
        f"link cuts: hit={grounded['prefix_hit_rate']*100:.0f}% "
        f"detours={grounded['detoured_ops']} "
        f"ground_hits={grounded['ground_hits']} "
        f"repaired_from_ground={grounded['repaired_from_ground']} "
        f"lost={grounded['lost_blocks']} | no-ground: "
        f"hit={bare['prefix_hit_rate']*100:.0f}% "
        f"lost={bare['lost_blocks']} | identical={identical}",
    ), (
        "degraded_fabric[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def _striped_directory(model, params, *, smoke: bool):
    """The metadata tier under fire: the directory is striped across the
    fabric (entry home = hash-derived stripe, ``dir_replication``
    plane-diverse copies), so losing satellites loses *metadata*, not
    just chunks.  Mid-serve we wipe BOTH homes of the busiest stripe on
    a dir_replication=2 cluster: lookups on that stripe degrade (probe
    the dead home, fall through), blocks whose entries are unreachable
    recompute -- every request still completes, tokens byte-identical to
    the fault-free run -- and after the homes heal, ``reconcile``
    rewrites the wiped stripe from inventory + the client journal.  The
    cluster runs over a write-through ground tier: a stripe's homes are
    the same satellites as its server's chunk homes, so the ground
    segment absorbs the collateral *data* loss and what this scenario
    isolates is the *metadata* failure mode.  A dir_replication=1 probe
    on the same geometry shows the contrast: one dead stripe home and
    its entries are simply gone, even though every chunk copy is still
    in orbit (metadata loss, not data loss)."""
    import hashlib

    from repro.core import (
        ConstellationKVC, ConstellationSpec, FaultInjector, FaultPlan,
        GroundStationTier, IslTransport, LosWindow, Sat, SimClock,
        Strategy, chain_hashes, stripe_of,
    )
    from repro.core.faults import FaultEvent
    from repro.serving import EngineCluster, Request, SamplingParams

    max_seq_len = 512
    block = 128
    groups = 5
    dup = 4
    gen_new = 4 if smoke else 8
    filler = ("SkyMemory stripes the block directory across the "
              "constellation: metadata is fabric state with homes, "
              "replicas, priced lookups, and an inventory-driven "
              "reconcile pass that rebuilds wiped stripes. ")
    spec = ConstellationSpec(15, 15, 550.0)

    def stream(rep: int):
        return [
            Request(prompt=f"[sd rep {rep} doc {i // dup}] " + filler * 2,
                    sampling=SamplingParams(max_new_tokens=gen_new))
            for i in range(groups * dup)
        ]

    def build():
        clock = SimClock(rate=5.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=2,
            dir_replication=2,
            transport=IslTransport(spec, clock=clock,
                                   chunk_processing_time_s=2e-4,
                                   probe_timeout_s=5e-3),
            ground=GroundStationTier(spec, processing_time_s=1e-3),
            ground_write="all",
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=2, policy="prefix_affinity",
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4,
        )
        for i, eng in enumerate(cluster.engines):   # warm compiles
            eng.generate([Request(prompt=f"[sd warm {i}] " + filler,
                                  sampling=SamplingParams(max_new_tokens=2))])
        # warm the cache + directory with the MEASURED stream: the
        # measured serve is then pure metadata-plane traffic (every
        # request resolves its prefix through a priced stripe lookup)
        cluster.serve(stream(1))
        cluster.reset_stats()
        return cluster, kvc

    def measure(faulted: bool) -> dict:
        cluster, kvc = build()
        # wipe the stripe that homes the most of the doc groups' tail-
        # block entries -- the hashes the serve will actually look up
        tails = [
            chain_hashes(cluster.engines[0].tokenizer.encode(
                f"[sd rep 1 doc {doc}] " + filler * 2), block)[-1]
            for doc in range(groups)
        ]
        sid = max(range(kvc.num_servers),
                  key=lambda s: sum(
                      stripe_of(t, kvc.num_servers) == s for t in tails))
        homes = [kvc.replica_sat(sid, r) for r in range(2)]
        inj = None
        if faulted:
            events = []
            # both kills due at the first fabric op of the serve: every
            # lookup the stream issues on the wiped stripe degrades
            for i, sat in enumerate(homes):
                events.append(
                    FaultEvent(at_s=i * 0.01, action="kill", sat=sat))
                events.append(FaultEvent(at_s=1e9, action="heal", sat=sat))
            inj = FaultInjector(kvc, FaultPlan(events))
            inj.arm()
        t0 = time.perf_counter()
        out = cluster.serve(stream(1))
        wall = time.perf_counter() - t0
        merged = cluster.merged_stats()
        run = {
            "tokens_per_s": sum(len(r.token_ids) for r in out) / wall,
            "requests": len(out),
            "completed": sum(1 for r in out if len(r.token_ids) > 0),
            "cached_tokens": merged.cached_tokens,
            "token_ids": [list(r.token_ids) for r in out],
            "wiped_stripe": sid,
        }
        if inj is not None:
            run["sat_kills"] = inj.stats.sat_kills
            run["dir_entries_dropped"] = inj.stats.dir_entries_dropped
            inj.drain()                      # the wiped homes come back
            run["shard_len_after_heal"] = kvc.dir_shard_len(homes[0])
            run["reconciled_chunks"] = kvc.reconcile()
            run["shard_len_after_reconcile"] = kvc.dir_shard_len(homes[0])
        fabric = cluster.fabric_stats()
        run.update({
            "prefix_hit_rate": fabric["prefix_hit_rate"],
            "dir_lookups": fabric["dir_lookups"],
            "degraded_lookups": fabric["degraded_lookups"],
            "dir_repaired_entries": fabric["dir_repaired_entries"],
            "orphaned_chunks": fabric["orphaned_chunks"],
            "degraded_reads": fabric["degraded_reads"],
            "ground_hits": fabric["ground_hits"],
            "lost_blocks": fabric["lost_blocks"],
        })
        return run

    def k1_probe() -> dict:
        # no model needed: a bare dir_replication=1 fabric with the same
        # geometry, to show one dead stripe home = entries gone even
        # though every chunk copy is still in orbit
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=2,
            dir_replication=1,
        )
        hashes = [hashlib.sha256(b"sd-probe-%d" % i).digest()
                  for i in range(20)]
        for i, h in enumerate(hashes):
            kvc.set_block(h, bytes([i % 251]) * (2 * 6 * 1024))
        sid = max(range(kvc.num_servers),
                  key=lambda s: kvc.dir_shard_len(kvc.server_sat(s)))
        inj = FaultInjector(kvc, FaultPlan.outages([kvc.server_sat(sid)]))
        inj.arm()
        inj.advance()
        resolvable = sum(1 for h in hashes if kvc.get_block(h) is not None)
        return {
            "entries": len(hashes),
            "entries_dropped": inj.stats.dir_entries_dropped,
            "resolvable_after_kill": resolvable,
        }

    baseline = measure(faulted=False)
    wiped = measure(faulted=True)
    probe = k1_probe()

    n_reqs = groups * dup
    identical = wiped["token_ids"] == baseline["token_ids"]
    acceptance = {
        # a stripe wipeout costs lookups and recomputes, never answers
        "all_requests_complete": all(
            run["completed"] == n_reqs for run in (baseline, wiped)),
        "outputs_byte_identical_to_fault_free": identical,
        "lookups_are_priced_fabric_ops": baseline["dir_lookups"] > 0,
        "degraded_lookups_nonzero": wiped["degraded_lookups"] > 0,
        "stripe_rebuilt_by_reconcile":
            wiped["dir_repaired_entries"] > 0
            and wiped["shard_len_after_reconcile"]
            > wiped["shard_len_after_heal"],
        "dir_k1_demonstrably_loses_entries":
            probe["entries_dropped"] > 0
            and probe["resolvable_after_kill"] < probe["entries"],
    }
    record = {
        "groups": groups, "dup_per_group": dup, "replicas": 2,
        "replication": 2, "dir_replication": 2,
        "unfaulted": {k: v for k, v in baseline.items()
                      if k != "token_ids"},
        "stripe_wiped": {k: v for k, v in wiped.items()
                         if k != "token_ids"},
        "dir_k1_probe": probe,
        "acceptance": acceptance,
    }
    rows = [(
        "striped_directory", 0.0,
        f"unfaulted hit={baseline['prefix_hit_rate']*100:.0f}% "
        f"dir_lookups={baseline['dir_lookups']} | stripe "
        f"{wiped['wiped_stripe']} wiped (entries_dropped="
        f"{wiped['dir_entries_dropped']}): "
        f"hit={wiped['prefix_hit_rate']*100:.0f}% "
        f"degraded_lookups={wiped['degraded_lookups']} "
        f"repaired_entries={wiped['dir_repaired_entries']} | k1 probe: "
        f"{probe['resolvable_after_kill']}/{probe['entries']} resolvable "
        f"after one stripe-home kill | identical={identical}",
    ), (
        "striped_directory[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def _quantized_payloads(model, params, *, smoke: bool):
    """The payload codec as a capacity/bandwidth multiplier: the SAME
    duplicated-context stream served three times over one capacity-bound
    constellation -- f32 (raw arrays), int8 (per-channel quantized,
    per-block scale tables), and int4+delta (nibble-packed, each
    cumulative block shipping only its own tokens).  Per-satellite
    capacity is sized so the f32 working set does NOT fit (LRU evicts
    mid-stream and the re-serve thrashes) while the int8 one does: at
    equal orbit, quantization buys a strictly higher hit rate and fewer
    ISL bytes, with byte-identical greedy outputs.  int4+delta trades
    more compression for quantization error, so its gate is determinism
    across runs, not f32-identity."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, LosWindow, Sat, Strategy,
        chain_hashes,
    )
    from repro.serving import ByteTokenizer, Engine, Request, SamplingParams
    from repro.serving.skycache import SkyKVCAdapter

    max_seq_len = 512
    block = 128
    groups = 4
    gen_new = 4 if smoke else 8
    num_servers = 10
    filler = ("SkyMemory ships quantized delta-encoded KVC payloads over "
              "the ISL fabric: per-block scale tables, self-describing "
              "headers, and a router that prices encoded bytes. ")
    spec = ConstellationSpec(15, 15, 550.0)

    def prompt(doc: int) -> str:
        return f"[qp doc {doc}] " + filler * 2

    def reqs():
        return [Request(prompt=prompt(i),
                        sampling=SamplingParams(max_new_tokens=gen_new))
                for i in range(groups)]

    # size the orbit against the f32 working set: cumulative payloads
    # cost bpt*bs*(1 + 2 + ... + n_blocks) bytes per doc, striped over
    # the chunk servers.  45% of that per-satellite need thrashes f32;
    # int8 needs ~25% and fits, int4+delta far less
    bpt_f32 = SkyKVCAdapter(model, params).payload_bytes_per_token()
    tok = ByteTokenizer(model.cfg.vocab_size)
    n_blocks = len(tok.encode(prompt(0))) // block
    per_doc = bpt_f32 * block * n_blocks * (n_blocks + 1) // 2
    cap = int(0.45 * groups * per_doc / num_servers)

    def run(codec: str) -> dict:
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=num_servers, chunk_bytes=6 * 1024,
            per_sat_capacity_bytes=cap,
        )
        eng = Engine(model, params, kvc=kvc, block_size=block,
                     max_seq_len=max_seq_len, max_batch=4,
                     payload_codec=codec)
        eng.generate([Request(prompt="[qp warm] " + filler,
                              sampling=SamplingParams(max_new_tokens=2))])
        out1 = eng.generate(reqs())              # populate (and evict...)
        t0 = time.perf_counter()
        out2 = eng.generate(reqs())              # re-serve: hits iff it fit
        wall = time.perf_counter() - t0
        cs, tr = kvc.stats, kvc.transport.stats
        # the router's price for a re-served doc's tail block: with the
        # registered payload_bytes being ENCODED sizes, the estimate and
        # the experienced fetch path agree on bytes by construction
        hashes = chain_hashes(tok.encode(prompt(0)), block)[:n_blocks]
        tail = kvc.get_block(hashes[-1])
        meta = eng.manager.index.longest_cached_prefix(hashes)[1]
        return {
            "codec": codec,
            "tokens_per_s": sum(len(r.token_ids) for r in out2) / wall,
            "hit_rate": (sum(r.cached_tokens for r in out2)
                         / max(sum(r.prompt_tokens for r in out2), 1)),
            "bytes_encoded": cs.bytes_encoded,
            "bytes_raw": cs.bytes_raw,
            "compression_ratio": cs.bytes_raw / max(cs.bytes_encoded, 1),
            "bytes_moved": tr.bytes_moved,
            "blocks_evicted": cs.blocks_purged,
            "dequant_overlap_s": eng.stats.dequant_overlap_s,
            "registered_bytes_are_encoded": (
                tail is not None and meta is not None
                and meta.payload_bytes == len(tail)),
            "token_ids": [list(r.token_ids) for r in out1 + out2],
        }

    f32 = run("f32")
    q8 = run("int8")
    q4a = run("int4+delta")
    q4b = run("int4+delta")

    acceptance = {
        # int8 encoded Set/Get bytes >= 3.5x smaller than the same
        # payloads raw (raw == what the f32 codec would have shipped)
        "int8_encoded_3p5x_smaller": q8["compression_ratio"] >= 3.5,
        "int8_outputs_byte_identical_to_f32":
            q8["token_ids"] == f32["token_ids"],
        "int8_hit_rate_strictly_higher_at_equal_capacity":
            q8["hit_rate"] > f32["hit_rate"],
        "int8_moves_fewer_isl_bytes":
            q8["bytes_moved"] < f32["bytes_moved"],
        "f32_thrashes_at_this_capacity": f32["blocks_evicted"] > 0,
        "int4_delta_deterministic_across_runs":
            q4a["token_ids"] == q4b["token_ids"],
        "int4_delta_compresses_harder":
            q4a["compression_ratio"] > q8["compression_ratio"],
        "router_prices_encoded_bytes": q8["registered_bytes_are_encoded"],
    }
    record = {
        "groups": groups, "blocks_per_doc": n_blocks,
        "per_sat_capacity_bytes": cap,
        "f32": {k: v for k, v in f32.items() if k != "token_ids"},
        "int8": {k: v for k, v in q8.items() if k != "token_ids"},
        "int4_delta": {k: v for k, v in q4a.items() if k != "token_ids"},
        "acceptance": acceptance,
    }
    rows = [(
        "quantized_payloads", 0.0,
        f"cap={cap//1024}KB/sat | f32 hit={f32['hit_rate']*100:.0f}% "
        f"moved={f32['bytes_moved']//1024}KB "
        f"evicted={f32['blocks_evicted']} | int8 "
        f"hit={q8['hit_rate']*100:.0f}% "
        f"moved={q8['bytes_moved']//1024}KB "
        f"ratio={q8['compression_ratio']:.2f}x identical="
        f"{q8['token_ids'] == f32['token_ids']} | int4+delta "
        f"ratio={q4a['compression_ratio']:.2f}x "
        f"hit={q4a['hit_rate']*100:.0f}% deterministic="
        f"{q4a['token_ids'] == q4b['token_ids']}",
    ), (
        "quantized_payloads[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def _sustained_load(model, params, *, smoke: bool):
    """Streaming serve under sustained overload: a seeded bursty
    multi-tenant arrival stream at ~1.2x the cluster's service capacity,
    run through ``serve_stream`` in the deterministic pump-budget mode
    (2 replicas over one clocked int8 fabric, rotation on).  Four bars:

    * goodput (SLO-attained tokens/s) beats the closed-batch baseline
      that must wait for the whole batch to arrive before serving;
    * per-request router release yields a strictly lower stream-wide
      ITL tail than holding every commitment to the end of the run
      (stale loads pile concurrent work onto one replica);
    * overload shedding never touches the protected tenant -- every
      ``pro`` request completes while low-priority arrivals shed;
    * the full record stream replays byte-identically for a fixed seed.

    Capacity is calibrated on THIS host by a closed-batch probe: the
    pump budget per virtual second is sized so arrivals outpace service
    rounds by 1.2x, which makes the overload (and with it the shed set)
    a pure function of the arrival history."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, IslTransport, LosWindow, Sat,
        SimClock, Strategy,
    )
    from repro.serving import (
        SLO, AdmissionController, EngineCluster, Request, SamplingParams,
        SLOTracker, TenantSpec, TrafficGenerator,
    )

    max_seq_len = 512
    block = 128
    clock_rate = 5.0
    n_requests = 24 if smoke else 48
    mnt = (2, 8, 4) if smoke else (8, 24, 12)   # pro / burst / diurnal
    overload = 1.2
    filler = ("SkyMemory serves an open request stream from orbit: "
              "arrivals route at arrival time, loads release per "
              "request, and overload sheds the lowest priority first. ")

    def build() -> EngineCluster:
        spec = ConstellationSpec(15, 15, 550.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024,
            transport=IslTransport(spec, clock=SimClock(rate=clock_rate),
                                   chunk_processing_time_s=2e-4),
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=2, policy="prefix_affinity",
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4, rotate_every_s=2.0, payload_codec="int8",
        )
        for i, eng in enumerate(cluster.engines):
            eng.generate([Request(prompt=f"[warm {i}] " + filler,
                                  sampling=SamplingParams(max_new_tokens=2))])
        cluster.reset_stats()
        return cluster

    # one seeded multi-tenant mix, 1 request per virtual second total:
    # a protected Poisson tenant, a bursty document-reuse tenant, and a
    # diurnal tenant, with heterogeneous generation lengths
    tenants = [
        TenantSpec(name="pro", rate_rps=0.25, process="poisson",
                   priority=1, max_new_tokens=mnt[0],
                   prompt_chars=(48, 96)),
        TenantSpec(name="burst", rate_rps=0.5, process="bursty",
                   burst_size=4, burst_spread_s=0.05,
                   prefix_reuse_p=0.6, num_documents=3,
                   max_new_tokens=mnt[1], prompt_chars=(48, 96)),
        TenantSpec(name="diurnal", rate_rps=0.25, process="diurnal",
                   diurnal_period_s=8.0, max_new_tokens=mnt[2],
                   prompt_chars=(48, 96)),
    ]
    arrivals = TrafficGenerator(tenants, seed=0).take(n_requests)
    t_last = arrivals[-1].t_s

    # ---- probe: this host's service rate in cluster pump rounds ------
    # submit a representative batch and count how many _pump_all rounds
    # drain it: service capacity in requests/round (batching included),
    # plus the wall cost of one round -- the two numbers the overload
    # knob and the SLO targets are derived from
    probe = build()
    probe_reqs = [Request(prompt=f"[probe {i}] " + filler,
                          sampling=SamplingParams(max_new_tokens=mnt[i % 3]))
                  for i in range(8)]
    for r in probe_reqs:
        probe.submit(r)
    rounds = 0
    t0 = time.perf_counter()
    while probe._pump_all():
        rounds += 1
    probe_wall = time.perf_counter() - t0
    rounds = max(rounds, 1)
    step_wall = probe_wall / rounds
    service_req_per_round = len(probe_reqs) / rounds
    # arrivals outpace service rounds by `overload`: pump budget per
    # virtual second = arrival rate / (service rate * overload)
    virtual_rate = sum(t.rate_rps for t in tenants)
    pump_steps_per_s = virtual_rate / (service_req_per_round * overload)

    # the admission cap bounds the queue to ~6 in-flight requests, so an
    # admitted request drains within a handful of rounds; the TTFT
    # target sits above that and far below the closed-batch penalty
    # (the arrival span in wall time)
    slo_ttft = max(1.0, 8.0 * step_wall)
    slos = {t.name: SLO(ttft_s=slo_ttft) for t in tenants}
    capacity_tokens = 600

    def stream_run(release_mode: str, *, parallel: bool,
                   admit: bool, arrs=None):
        cluster = build()
        report = cluster.serve_stream(
            arrs if arrs is not None else arrivals,
            parallel=parallel, slos=slos,
            admission=AdmissionController(capacity_tokens=capacity_tokens,
                                          protect_priority=1)
            if admit else None,
            release_mode=release_mode,
            pump_steps_per_s=pump_steps_per_s)
        fp = [(r.arrival.tenant, r.shed,
               r.decision.replica if r.decision else None,
               tuple(r.result.token_ids) if r.result else None)
              for r in report.records]
        return report, fp

    report_pr, fp_a = stream_run("per_request", parallel=False, admit=True)
    _, fp_b = stream_run("per_request", parallel=False, admit=True)

    # ---- release-mode ITL comparison: realtime worker loops ----------
    # the deterministic single-threaded pump serializes both replicas
    # into one round, so routing balance cannot move ITL there.  With
    # live workers the effect is a drain asymmetry: one replica grinds a
    # long "hog" request while short requests arrive at ~capacity.
    # Per-request release keeps the hog's commitment visible and the
    # shorts' releases flowing, so shorts route to the free replica and
    # every engine decodes at batch ~1.  End-of-run release freezes
    # loads into cumulative counters: the router alternates shorts onto
    # the hog's replica, deepening its batch and stretching every
    # co-resident's inter-token gaps.  Same arrivals, no admission:
    # identical served sets, the release policy is the only difference
    from repro.serving import Arrival

    probe2 = build()
    t0 = time.perf_counter()
    probe2.serve([Request(prompt="[probe short] " + filler,
                          sampling=SamplingParams(max_new_tokens=mnt[2]))],
                 parallel=False)
    short_wall = time.perf_counter() - t0
    hog = Request(tenant="hog", prompt="[hog] " + filler * 4,
                  sampling=SamplingParams(max_new_tokens=12 * mnt[2]))
    n_shorts = 8 if smoke else 12
    itl_arrs = [Arrival(t_s=0.0, tenant="hog", request=hog)] + [
        Arrival(t_s=(i + 1) * short_wall * clock_rate, tenant="short",
                request=Request(tenant="short",
                                prompt=f"[short {i}] " + filler,
                                sampling=SamplingParams(
                                    max_new_tokens=mnt[2])))
        for i in range(n_shorts)
    ]
    report_live_pr, _ = stream_run("per_request", parallel=True,
                                   admit=False, arrs=itl_arrs)
    report_eor, _ = stream_run("end_of_run", parallel=True,
                               admit=False, arrs=itl_arrs)

    # ---- closed-batch baseline on the SAME stream --------------------
    # a closed batch cannot start before its last member arrives: each
    # request eats the wall-time remainder of the arrival span on top of
    # its in-batch TTFT, and the run spans arrivals + serve
    base = build()
    t0 = time.perf_counter()
    base_out = base.serve([a.request for a in arrivals], parallel=False)
    base_wall = time.perf_counter() - t0
    span_wall = t_last / clock_rate
    base_tracker = SLOTracker(slos)
    for a, r in zip(arrivals, base_out):
        base_tracker.note_offered(a.tenant)
        base_tracker.observe(
            a.tenant,
            ttft_s=r.ttft_s + (t_last - a.t_s) / clock_rate,
            itl_samples_s=r.itl_samples_s,
            new_tokens=len(r.token_ids))
    base_slo = base_tracker.report(span_wall + base_wall)

    # streaming overlaps service with the arrival span; charge it the
    # span if compute finished inside it (open-loop elapsed time)
    stream_elapsed = max(report_pr.elapsed_s, span_wall)
    stream_goodput = (report_pr.slo["goodput_tokens_per_s"]
                      * report_pr.elapsed_s / stream_elapsed)
    goodput_ratio = stream_goodput / max(
        base_slo["goodput_tokens_per_s"], 1e-9)

    itl_pr = report_live_pr.slo["itl_tail_s"]["p95"]
    itl_eor = report_eor.slo["itl_tail_s"]["p95"]
    pro = report_pr.slo["per_tenant"]["pro"]

    acceptance = {
        "goodput_ge_1p1x_closed_batch": goodput_ratio >= 1.1,
        "per_request_release_improves_tail_itl": itl_pr < itl_eor,
        "overload_shed_someone": report_pr.slo["shed"] > 0,
        "protected_tenant_never_shed":
            pro["shed"] == 0 and pro["completed"] == pro["offered"],
        "deterministic_replay_byte_identical": fp_a == fp_b,
    }
    record = {
        "requests": n_requests,
        "overload_factor": overload,
        "pump_steps_per_s": pump_steps_per_s,
        "service_requests_per_round": service_req_per_round,
        "round_wall_s": step_wall,
        "probe_wall_s": probe_wall,
        "slo_ttft_s": slo_ttft,
        "capacity_tokens": capacity_tokens,
        "arrival_span_wall_s": span_wall,
        "rotations": report_pr.rotations,
        "streaming": report_pr.slo,
        "streaming_goodput_tokens_per_s": stream_goodput,
        "realtime_per_request_release": report_live_pr.slo,
        "realtime_end_of_run_release": report_eor.slo,
        "closed_batch_baseline": base_slo,
        "goodput_ratio_vs_closed_batch": goodput_ratio,
        "acceptance": acceptance,
    }
    s = report_pr.slo
    rows = [(
        "sustained_load", 0.0,
        f"goodput={stream_goodput:.1f}tok/s "
        f"(batch={base_slo['goodput_tokens_per_s']:.1f}, "
        f"ratio={goodput_ratio:.2f}x) "
        f"attainment={s['attainment']*100:.0f}% "
        f"shed={s['shed']}/{s['offered']} pro_shed={pro['shed']} | "
        f"itl_p95 per_req={itl_pr*1e3:.1f}ms "
        f"end_of_run={itl_eor*1e3:.1f}ms | "
        f"rotations={report_pr.rotations}",
    ), (
        "sustained_load[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def _chaos_sustained_load(model, params, *, smoke: bool):
    """Chaos under sustained load: the full composite fault arc --
    satellite kills, link cuts, a directory-stripe wipeout, and a
    replica-home-pair kill forcing ground fall-through -- driven through
    ``serve_stream``'s deterministic pump-budget mode mid-overload
    (2-replica clocked int8 fabric over a write-through ground tier,
    bursty multi-tenant mix offered at ~1.2x the probe-calibrated
    service rate).  The windowed goodput timeline tags every fixed
    virtual-time window pre_churn / churn / post_heal, and the bars are
    ratios of *goodput retention* (attained tokens per offered request,
    which cancels burst-volume noise between windows) across phases,
    after discarding the first two windows as queue-fill warmup:

    * retention through the churn windows holds >= 70% of pre-churn and
      recovers to >= 90% after the heals land (repair-on-heal), i.e.
      the fabric absorbs the arc -- replica fall-through, ground
      fall-through, repair -- without denting the goodput timeline;
    * the protected tenant sheds nothing and no admitted request fails,
      all the way through the arc;
    * the whole run -- records, fault counters, windowed timeline --
      replays byte-identically for the same (traffic seed, fault seed);
    * a k=1 control on the same geometry demonstrably degrades further:
      with no surviving orbital replica it loses more of its repair
      sources (fewer repaired chunks, and a strictly larger share of
      the survivors must be rebuilt from the ground segment) while
      holding at most the replicated fabric's churn retention.

    Capacity is probe-calibrated on the first arrivals of the actual
    stream (representative prompts, not synthetic fillers); with every
    SLO target open (inf) attained == completed, so the phase bars
    measure admission/shedding behaviour, not host wall noise.  The
    workload is identical in smoke and full modes: the bars are
    calibrated against this fixed seeded stream, and only the model
    (and hence the probe-measured service rate) changes."""
    from repro.core import (
        ConstellationKVC, ConstellationSpec, FaultPlan, GroundStationTier,
        IslTransport, LosWindow, Sat, SimClock, Strategy,
    )
    from repro.serving import (
        AdmissionController, EngineCluster, Request, SamplingParams,
        TrafficGenerator, standard_tenants,
    )

    max_seq_len = 512
    block = 64          # doc prefixes must span whole blocks to cache
    clock_rate = 5.0
    n_requests = 96
    max_new = 4
    overload = 1.2
    n_windows = 8       # 2 warmup+pre, 2 pre, 2 churn, 2 post-heal

    def build(k: int) -> EngineCluster:
        spec = ConstellationSpec(15, 15, 550.0)
        kvc = ConstellationKVC(
            spec, LosWindow(Sat(7, 7), 9, 9), Strategy.ROTATION_HOP,
            num_servers=10, chunk_bytes=6 * 1024, replication=k,
            dir_replication=k,
            transport=IslTransport(spec, clock=SimClock(rate=clock_rate),
                                   chunk_processing_time_s=2e-4,
                                   probe_timeout_s=5e-3),
            ground=GroundStationTier(spec, processing_time_s=1e-3),
            ground_write="all",
        )
        cluster = EngineCluster(
            model, params, kvc, num_replicas=2, policy="prefix_affinity",
            router_seed=0, block_size=block, max_seq_len=max_seq_len,
            max_batch=4, rotate_every_s=2.0, payload_codec="int8",
            num_pages=25,
        )
        for i, eng in enumerate(cluster.engines):
            eng.generate([Request(prompt=f"[warm {i}] chaos warm",
                                  sampling=SamplingParams(max_new_tokens=2))])
        cluster.reset_stats()
        return cluster

    # the standard 4-tenant mix (protected pro + bursty + diurnal) at 4
    # requests per virtual second, with the *protected* tenant carrying
    # fattened shared documents (multi-block prefixes): its cache mass
    # is what the fault arc attacks, and its zero-shed bar is what the
    # admission controller must hold through the churn.  seed 11 spreads
    # arrivals evenly across the 8 windows (no end-of-stream burst
    # clump that would confound the post-heal windows with drain sheds)
    tenants = standard_tenants(4, 4.0, max_new_tokens=max_new,
                               prompt_chars=(48, 96), prefix_reuse_p=0.5)
    tenants[0] = dataclasses.replace(tenants[0], prefix_reuse_p=0.9,
                                     num_documents=2, doc_chars=320)
    arrivals = TrafficGenerator(tenants, seed=11).take(n_requests)
    t_last = arrivals[-1].t_s
    # epsilon keeps the final arrival inside window n_windows-1 instead
    # of opening a degenerate extra window at exactly t_last
    window_s = t_last / n_windows * (1.0 + 1e-9)
    churn_start = 4.0 * window_s
    heal_at = 6.0 * window_s

    # ---- probe: this host's service rate on representative requests --
    probe = build(2)
    for a in arrivals[:8]:
        probe.submit(Request(prompt=a.request.prompt,
                             sampling=a.request.sampling,
                             priority=a.request.priority,
                             tenant=a.request.tenant))
    rounds = 0
    while probe._pump_all():
        rounds += 1
    service_req_per_round = 8 / max(rounds, 1)
    virtual_rate = sum(t.rate_rps for t in tenants)
    pump_steps_per_s = virtual_rate / (service_req_per_round * overload)
    # tight enough that the admission controller visibly sheds filler
    # under the sustained overload, loose enough that the steady-state
    # backlog does not swamp the post-heal windows with tail sheds
    capacity_tokens = 3900

    def arc(kvc) -> FaultPlan:
        return FaultPlan.chaos_arc(
            kvc, seed=29, churn_start_s=churn_start,
            churn_window_s=window_s, heal_s=heal_at,
            n_sat_kills=2, n_link_cuts=2, dir_stripe_wipeout=True,
            ground_pair_server=4)

    def run(k: int):
        cluster = build(k)
        report = cluster.serve_stream(
            arrivals, parallel=False,
            admission=AdmissionController(capacity_tokens=capacity_tokens,
                                          protect_priority=1),
            pump_steps_per_s=pump_steps_per_s,
            faults=arc(cluster.kvc), slo_window_s=window_s)
        fp = [(r.arrival.tenant, r.shed,
               r.decision.replica if r.decision else None,
               tuple(r.result.token_ids) if r.result else None)
              for r in report.records]
        cached = sum(r.cached_tokens for r in report.results())
        return report, fp, cached

    report, fp_a, cached_k2 = run(2)
    report_b, fp_b, _ = run(2)
    report_k1, _, cached_k1 = run(1)

    def phase_retention(rep) -> dict:
        """Attained tokens per offered request per phase, skipping the
        first ``warmup`` windows (queue still filling, retention
        artificially high)."""
        rows_w = sorted(rep.slo["windows"], key=lambda r: r["t0_s"])
        agg: dict[str, list[int]] = {}
        for i, r in enumerate(rows_w):
            if i < 2:
                continue
            a = agg.setdefault(r["phase"], [0, 0])
            a[0] += r["attained_tokens"]
            a[1] += r["offered"]
        return {ph: v[0] / max(v[1], 1) for ph, v in agg.items()}

    ret = phase_retention(report)
    churn_ratio = ret["churn"] / max(ret["pre_churn"], 1e-9)
    heal_ratio = ret["post_heal"] / max(ret["pre_churn"], 1e-9)
    ret_k1 = phase_retention(report_k1)
    churn_ratio_k1 = ret_k1["churn"] / max(ret_k1["pre_churn"], 1e-9)

    def ground_repair_frac(f) -> float:
        return f["repaired_from_ground"] / max(f["repaired_chunks"], 1)

    pro = report.slo["per_tenant"]["pro"]
    served = [r for r in report.records if not r.shed]
    f2, f1 = report.faults, report_k1.faults

    acceptance = {
        "goodput_holds_70pct_through_churn": churn_ratio >= 0.70,
        "goodput_recovers_90pct_post_heal": heal_ratio >= 0.90,
        "protected_tenant_never_shed":
            pro["shed"] == 0 and pro["completed"] == pro["offered"],
        "zero_failed_requests":
            all(r.result is not None and len(r.result.token_ids) > 0
                for r in served),
        "deterministic_replay_byte_identical":
            fp_a == fp_b and report.faults == report_b.faults
            and report.slo["windows"] == report_b.slo["windows"],
        "arc_actually_bit":
            f2["sat_kills"] >= 2 and f2["sat_heals"] >= 2
            and f2["link_kills"] >= 1 and f2["chunks_dropped"] > 0
            and f2["degraded_reads"] + f2["degraded_lookups"]
            + f2["ground_hits"] > 0,
        "k1_control_degrades_further":
            f1["repaired_chunks"] < f2["repaired_chunks"]
            and ground_repair_frac(f1) > ground_repair_frac(f2)
            and churn_ratio_k1 <= churn_ratio + 1e-9,
    }
    record = {
        "requests": n_requests,
        "overload_factor": overload,
        "pump_steps_per_s": pump_steps_per_s,
        "service_requests_per_round": service_req_per_round,
        "capacity_tokens": capacity_tokens,
        "window_s": window_s,
        "churn_start_s": churn_start,
        "heal_at_s": heal_at,
        "rotations": report.rotations,
        "faults": report.faults,
        "streaming": report.slo,
        "phase_retention_tokens_per_offered": ret,
        "churn_over_pre_ratio": churn_ratio,
        "post_heal_over_pre_ratio": heal_ratio,
        "cached_tokens_k2": cached_k2,
        "ground_repair_fraction_k2": ground_repair_frac(f2),
        "k1_control": {
            "faults": report_k1.faults,
            "phase_retention_tokens_per_offered": ret_k1,
            "churn_over_pre_ratio": churn_ratio_k1,
            "cached_tokens": cached_k1,
            "ground_repair_fraction": ground_repair_frac(f1),
            "shed": report_k1.slo["shed"],
        },
        "acceptance": acceptance,
    }
    s = report.slo
    rows = [(
        "chaos_sustained_load", 0.0,
        f"churn/pre={churn_ratio:.2f} post_heal/pre={heal_ratio:.2f} "
        f"shed={s['shed']}/{s['offered']} pro_shed={pro['shed']} "
        f"kills={f2['sat_kills']} degraded={f2['degraded_reads']} "
        f"ground_hits={f2['ground_hits']} "
        f"repaired={f2['repaired_chunks']} "
        f"(ground {ground_repair_frac(f2):.2f}) | "
        f"k1: churn/pre={churn_ratio_k1:.2f} "
        f"repaired={f1['repaired_chunks']} "
        f"(ground {ground_repair_frac(f1):.2f})",
    ), (
        "chaos_sustained_load[acceptance]", 0.0,
        " ".join(f"{k}={v}" for k, v in acceptance.items()),
    )]
    return rows, record


def tpu_strategy_costs():
    from repro.core.tpu_cache import TorusGrid, strategy_cost_table

    grid = TorusGrid(16, 16)
    costs = strategy_cost_table(grid, num_shards=64,
                                bytes_per_shard=2 * 1024 * 1024)
    us = _time_us(lambda: strategy_cost_table(grid, 64, 2 * 1024 * 1024))
    return [(
        "tpu_strategy_costs", us,
        " ".join(f"{k.split('(')[0]}={v*1e6:.1f}us" for k, v in costs.items()),
    )]


def protocol_micro():
    from repro.core import (
        ConstellationKVC, ConstellationSpec, LosWindow, Sat, Strategy,
        chain_hashes,
    )

    spec = ConstellationSpec(15, 15, 550.0)
    kvc = ConstellationKVC(spec, LosWindow(Sat(7, 7), 9, 9),
                           Strategy.ROTATION_HOP, num_servers=10,
                           chunk_bytes=6 * 1024)
    payload = b"x" * (128 * 1024)
    h = chain_hashes(list(range(128)), 128)[0]
    kvc.set_block(h, payload)
    rows = []
    rows.append(("protocol_set_128kB",
                 _time_us(lambda: kvc.set_block(h, payload), iters=20),
                 f"chunks={kvc.directory[h]}"))
    rows.append(("protocol_get_128kB",
                 _time_us(lambda: kvc.get_block(h), iters=20),
                 f"sim_latency={kvc.transport.stats.last_latency_s*1e3:.2f}ms"))
    pct = kvc.transport.stats.latency_percentiles()
    rows.append(("protocol_op_latency_pcts", 0.0,
                 f"p50={pct['p50']*1e3:.2f}ms p95={pct['p95']*1e3:.2f}ms "
                 f"p99={pct['p99']*1e3:.2f}ms "
                 f"(reservoir of {len(kvc.transport.stats.op_latencies_s)} "
                 f"over {kvc.transport.stats.ops} ops)"))
    hashes = chain_hashes(list(range(128 * 64)), 128)
    rows.append(("protocol_hash_64blocks",
                 _time_us(lambda: chain_hashes(list(range(128 * 64)), 128),
                          iters=10),
                 f"blocks={len(hashes)}"))
    rows.append(("protocol_rotate",
                 _time_us(lambda: kvc.rotate(1), iters=5),
                 f"migrations={kvc.stats.migrations}"))
    return rows


BENCHES = [
    fig1_2_isl_latency,
    table1_memory_tiers,
    fig16_strategy_sim,
    tpu_strategy_costs,
    protocol_micro,
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", dest="quick", action="store_false",
                    default=True, help="full-size TinyLlama for Table 3")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny model for the serving benchmark, "
                         "skip the slow Table-3 end-to-end run")
    args = ap.parse_args()

    enable_compile_cache()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        for name, us, derived in bench():
            print(f"{name},{us:.1f},{derived}")
    for name, us, derived in serving_throughput(
            quick=args.quick, smoke=args.smoke):
        print(f"{name},{us:.1f},{derived}")
    if not args.smoke:
        for name, us, derived in table3_kvc_speedup(quick=args.quick):
            print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
