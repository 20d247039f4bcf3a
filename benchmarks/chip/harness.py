"""One run of one cell: set-up, the measured window, the readers, the
check against the reference, and the result line.

``BENCHMARK.json`` names the cell's configuration and traffic; the
harness finds ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` by those names, so a later cell or metric is new
files and never an edit here.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (HERE, HERE / "metrics"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# host spans the benchmark puts around the program's layers; the trace's
# idle gaps are named by them
SPANS = ("submit", "lookup", "restore_decode", "page_import", "write_back",
         "step", "chunk_wave")


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``, or
    at the one fixed place in the checkout, holding every program however
    small or quick to compile, so that only a checkout's first run
    compiles.  Call before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class Refused(RuntimeError):
    """The run cannot be made here (no chip, a bad cell name)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    metrics: list[dict]          # the BENCHMARK.json entries to report
    chips: int


def cell(name: str, trace: bool, bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with the metrics that a
    run with or without ``--trace`` reports in it."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise Refused(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if trace:
        names = {m["name"] for m in e2e}
        metrics = [m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])
                   and m["moves"] in names]
    else:
        metrics = e2e
    return Cell(name=name, config=load_json(ROOT / conf["file"]),
                traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                metrics=metrics, chips=w["chips"])


def device_info(chips: int, require_tpu: bool) -> dict:
    """The devices JAX sees, or ``Refused`` when the cell's chips are not
    all there (never a fall-back to the CPU)."""
    import jax

    if require_tpu and "REPRO_KERNEL_IMPL" in os.environ:
        raise Refused("REPRO_KERNEL_IMPL is set: kernels must not be "
                      "replaced on the chip")
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX sees "
                      f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_for(kind: str, require_tpu: bool) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if require_tpu:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return {"bf16_flops": float("nan"), "hbm_bytes_s": float("nan")}


# ---------------------------------------------------------------------------
# instrumentation of the program, from outside it
# ---------------------------------------------------------------------------

class Probe:
    """Host spans around the program's layers and the shapes of the
    ``chunked_prefill_paged`` calls its step programs make, recorded only
    while a trace is being taken."""

    def __init__(self) -> None:
        self.on = False
        self.calls: list[tuple[np.ndarray, np.ndarray]] = []

    def wrap(self, obj, attr: str, span: str, record=None) -> None:
        import jax

        fn = getattr(obj, attr)
        probe = self

        def wrapped(*a, **k):
            if not probe.on:
                return fn(*a, **k)
            if record is not None:
                record(*a, **k)
            with jax.profiler.TraceAnnotation(span):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)

    def attach(self, cluster) -> None:
        eng = cluster.engines[0]
        sched = eng.scheduler

        def on_step(bt, lens, toks, *a, chunk_ops=None, **k):
            pos = sched._lengths.copy()
            self.calls.append((pos, np.ones_like(pos)))
            if chunk_ops is not None:
                off = np.asarray(chunk_ops[2])
                self.calls.append((off, np.asarray(chunk_ops[3])))

        def on_wave(buf, bts, offs, valids):
            self.calls.append((np.asarray(offs), np.asarray(valids)))

        self.wrap(cluster, "submit", "submit")
        self.wrap(eng.kv, "lookup_prefix", "lookup")
        self.wrap(eng.adapter, "payload_to_pages", "restore_decode")
        self.wrap(eng.cache, "write_pages", "page_import")
        self.wrap(eng.manager, "kvc_fn", "write_back")
        self.wrap(eng.executor, "step", "step", on_step)
        self.wrap(eng.executor, "chunk_wave", "chunk_wave", on_wave)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class RunData:
    """What the metric readers read."""

    cfg: dict
    peak: dict
    recs: list
    t0: float
    t_end: float
    setup_s: float
    stats: dict
    window_compiles: int
    trace: dict | None = None
    trace_s: float | None = None
    busy_s: float | None = None
    trace_bounds: tuple[int, int] | None = None
    kernel_calls: list = field(default_factory=list)


def _stats(cluster) -> dict:
    import dataclasses

    s = cluster.merged_stats()
    return {f.name: getattr(s, f.name) for f in dataclasses.fields(s)
            if not isinstance(getattr(s, f.name), list)}


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Served:
    """A cell's system under test after set-up, ready for a window."""

    c: Cell
    seed: int
    device: dict
    peak: dict
    cluster: object
    traffic: object
    probe: Probe
    compiles: dict


def prepare(c: Cell, seed: int, require_tpu: bool = True) -> Served:
    """Set-up: weights from the seed, the cluster with its pool, the
    documents stored, every shape the traffic uses warmed."""
    import jax

    import program
    import reference
    import serve
    from traffic import Traffic, tokens

    device = device_info(c.chips, require_tpu)
    peak = peak_for(device["kind"], require_tpu)
    dep = c.config["deployment"]
    # a program built in the window either compiles or is loaded from
    # the persistent cache; only the first kind is counted as a compile
    compiles = {"on": False, "built": 0, "loaded": 0}

    def on_duration(name, secs, **_):
        if name == COMPILE_EVENT and compiles["on"]:
            compiles["built"] += 1

    def on_event(name, **_):
        if name == CACHE_HIT_EVENT and compiles["on"]:
            compiles["loaded"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR", HERE / "none"))
    cold = not (cache.is_dir() and any(cache.iterdir()))

    clock = {"t": time.perf_counter()}

    def phase(name: str) -> None:
        now = time.perf_counter()
        _say(f"[setup] {name}: {now - clock['t']!r} s")
        clock["t"] = now

    params = reference.make_params(c.config, seed)
    mcfg = program.model_config(c.config)
    model = program.Model(mcfg)
    program.check_layout(model, params)
    phase("weights")
    pages, sizes = program.pool_pages(model, params, dep)
    phase("pool sizing")
    if cold and sizes is not None:
        # a first run: every step program at the run's pool, compiled
        # side by side into the persistent cache before the warm-up loads
        # them one by one
        program.compile_all(program.step_programs(model, params, dep, pages,
                                                  every_shape=True))
        phase("step programs compiled")
    cluster = program.build_cluster(model, params, dep, pages, seed)
    del params
    pool_bytes = sum(e.cache.k_pool.nbytes + e.cache.v_pool.nbytes
                     for e in cluster.engines)
    _say(f"[setup] pool {pages} pages, {pool_bytes} bytes; sizes the pool "
         f"was taken from (bytes): {sizes}")
    traf = Traffic(c.traffic, seed)
    probe_text = traf.documents[0][:300]
    if program.tokenize(cluster, probe_text) != tokens(probe_text):
        raise RuntimeError("the program tokenizes prompts differently")
    phase("cluster")
    serve.setup(cluster, traf, dep, phase)
    stats = jax.devices()[0].memory_stats() or {}
    _say(f"[setup] device memory after warm-up: peak "
         f"{stats.get('peak_bytes_in_use')} of {stats.get('bytes_limit')}")
    probe = Probe()
    probe.attach(cluster)
    gc.collect()
    return Served(c=c, seed=seed, device=device, peak=peak, cluster=cluster,
                  traffic=traf, probe=probe, compiles=compiles)


def window(sv: Served, seconds: float, trace: bool, t0: float,
           setup_s: float) -> RunData:
    """The measured window from host time ``t0``, and the drain after
    it.  The cluster stays alive."""
    import jax

    import serve
    from devtrace import WINDOW_SPAN

    c, cluster, probe, compiles = sv.c, sv.cluster, sv.probe, sv.compiles
    t_end = t0 + seconds
    snap = {}
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    ann = {}
    trace_len = min(8.0, seconds / 2)

    def trace_on():
        jax.profiler.start_trace(tdir)
        ann["a"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        ann["a"].__enter__()
        ann["t"] = time.perf_counter()
        probe.on = True

    def trace_off():
        probe.on = False
        ann["a"].__exit__(None, None, None)
        ann["t"] = time.perf_counter() - ann["t"]
        jax.profiler.stop_trace()

    def close():
        snap.update(_stats(cluster))
        compiles["on"] = False

    events = [(t_end, close)]
    if trace:
        events += [(t_end - trace_len, trace_on), (t_end - 1e-3, trace_off)]
    timeline = serve.Timeline(events)
    cluster.reset_stats()
    compiles.update(on=True, built=0, loaded=0)
    cluster.start_workers()
    try:
        if c.traffic["loop"] == "open":
            recs = serve.open_loop(cluster, sv.traffic.open_loop(seconds),
                                   t0, seconds, timeline)
        else:
            recs = serve.closed_loop(cluster, sv.traffic, t0, seconds,
                                     timeline)
        serve.collect(recs, t_end + c.traffic.get("drain_s", 90.0))
        _say(f"[window] drained {time.perf_counter() - t_end!r} s after "
             f"the close")
    finally:
        cluster.stop_workers(drain=False)
    errors = [r.error for r in recs if r.error is not None]
    if errors:
        _say(f"[window] {len(errors)} requests failed; first: {errors[0]!r}")
    late = np.asarray([r.sent - r.due for r in recs])
    _say(f"[window] {len(recs)} requests over {seconds} s; generator "
         f"late p50 {float(np.median(late))!r} s, max {float(late.max())!r} s"
         f"; programs built {compiles['built']}, of them loaded from the "
         f"compile cache {compiles['loaded']}")

    data = RunData(cfg=c.config, peak=sv.peak, recs=recs, t0=t0,
                   t_end=t_end, setup_s=setup_s, stats=snap,
                   window_compiles=compiles["built"] - compiles["loaded"])
    if trace:
        import shutil

        import devtrace

        data.trace = devtrace.load(tdir, set(SPANS) | {WINDOW_SPAN})
        _say("[trace] device planes: " + ", ".join(
            f"{dev} ({', '.join(f'{k} {len(v)}' for k, v in lines.items())})"
            for dev, lines in data.trace["devices"].items()))
        win = [s for s in data.trace["spans"] if s[0] == WINDOW_SPAN]
        lo, hi = (win[0][1], win[0][1] + win[0][2]) if win else (0, 2 ** 62)
        data.trace_s = (hi - lo) / 1e9 if win else ann["t"]
        data.busy_s = devtrace.busy_s(data.trace, lo, hi)
        data.kernel_calls = probe.calls
        data.trace_bounds = (lo, hi)
        shutil.rmtree(tdir, ignore_errors=True)
    return data


def serve_window(c: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, require_tpu: bool = True):
    """Set-up and the window.  Returns ``(run_data, device, cluster)``;
    the cluster is still alive."""
    sv = prepare(c, seed, require_tpu)
    t0 = time.perf_counter() + 0.05
    data = window(sv, seconds, trace, t0, t0 - t_start)
    return data, sv.device, sv.cluster


def failed(rec) -> bool:
    """A request that raised, never finished, or stopped short."""
    r = rec.result
    if r is None:
        return True
    return (len(r.token_ids) < rec.spec.max_new_tokens
            and r.finish_reason not in ("eos", "max_seq_len"))


def check(c: Cell, seed: int, data: RunData, control: bool = False
          ) -> dict:
    """The served tokens of a sample of finished requests against the
    reference's greedy choice.  Returns the numbers compared (and, with
    ``control``, the same number for the float8 control)."""
    import reference
    from traffic import tokens

    chk = c.traffic["check"]
    done = [r for r in data.recs if not failed(r)]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC4EC]))
    sample = []
    if done:
        longest = max(range(len(done)), key=lambda i: (
            done[i].spec.prompt_tokens + len(done[i].result.token_ids)))
        rest = [i for i in range(len(done)) if i != longest]
        k = min(chk["requests"] - 1, len(rest))
        sample = [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                          replace=False)]
    params = reference.make_params(c.config, seed)
    dep = c.config["deployment"]
    gaps, cgaps, restored, n_tok = [], [], 0, 0
    for i in sample:
        r = done[i]
        out = list(r.result.token_ids)
        seq = tokens(r.spec.prompt) + out[:-1]
        first = len(seq) - len(out)
        kw = dict(rows=dep["check_rows"], pad_to=dep["max_seq_len"])
        ref = reference.logits_at(params, c.config, seq, first, len(out),
                                  **kw)
        g = reference.greedy_gaps(ref, out)
        gaps.append(float(g.max()))
        n_tok += len(out)
        restored += r.result.cached_tokens > 0
        if control:
            low = reference.logits_at(params, c.config, seq, first,
                                      len(out), fp8=True, **kw)
            cgaps.append(float(reference.greedy_gaps(
                ref, low.argmax(-1)).max()))
        _say(f"[check] request {r.spec.index}: {r.spec.prompt_tokens} "
             f"prompt tokens ({r.result.cached_tokens} restored), "
             f"{len(out)} served, widest gap {gaps[-1]!r} std"
             + (f", control {cgaps[-1]!r} std" if control else ""))
    out = {"max_gap_std": max(gaps) if gaps else None,
           "checked_tokens": n_tok, "restored_checked": restored}
    if control:
        out["control_max_gap_std"] = max(cgaps) if cgaps else None
    return out


def verdict(c: Cell, data: RunData, numbers: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit, and whether all hold."""
    chk = c.traffic["check"]
    n_failed = sum(failed(r) for r in data.recs)
    gap = numbers["max_gap_std"]
    checks = {
        "failed": {"value": n_failed, "limit": 0},
        "max_gap_std": {"value": gap, "limit": chk["max_gap_std"]},
        "checked_tokens": {"value": numbers["checked_tokens"],
                           "limit": chk["min_tokens"]},
        "restored_checked": {"value": numbers["restored_checked"],
                             "limit": chk["min_restored"]},
    }
    ok = (n_failed == 0 and gap is not None and gap <= chk["max_gap_std"]
          and numbers["checked_tokens"] >= chk["min_tokens"]
          and numbers["restored_checked"] >= chk["min_restored"])
    return ok, checks


def free(cluster) -> None:
    """Drop the program's device state before the reference runs."""
    for e in cluster.engines:
        e.cache.k_pool.delete()
        e.cache.v_pool.delete()
        for leaf in __import__("jax").tree.leaves(e.params):
            leaf.delete()
    gc.collect()


def read_metrics(c: Cell, data: RunData) -> dict:
    out = {}
    for m in c.metrics:
        v = importlib.import_module(m["name"]).read(data)
        if v is not None and np.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(c: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """The whole run; returns the result object the last line prints."""
    import jax

    import devtrace

    data, device, cluster = serve_window(c, seed, seconds, trace, t_start,
                                         require_tpu)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    metrics = read_metrics(c, data)
    result = {"correct": False, "attempted": len(data.recs),
              "failed": sum(failed(r) for r in data.recs),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = data.busy_s
        device["window_s"] = data.trace_s
        lo, hi = data.trace_bounds
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(data.trace),
            "idle_gaps": devtrace.idle_gaps(data.trace, lo, hi)}
    cluster.stop_workers(drain=False)
    free(cluster)
    del cluster
    t = time.perf_counter()
    numbers = check(c, seed, data)
    _say(f"[check] {time.perf_counter() - t!r} s")
    ok, checks = verdict(c, data, numbers)
    result["correct"] = ok
    result["checks"] = checks
    for k, v in checks.items():
        _say(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    return result
