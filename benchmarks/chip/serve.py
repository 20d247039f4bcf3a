"""Set-up and the measured window of one run, through the program's front
door (``EngineCluster.submit``).

Set-up stores the cell's documents through the served write path, then
warms every shape the window can use: the restore of each document size,
the write-back of every block position a prompt can reach, the decode,
mixed and chunk-wave programs at every buffer and row count the scheduler
pads to, and the first-token sampler at every wave size.

The window records, for every request, when it was due, when it was
handed to the cluster and when it finished; the program's result gives
the first-token delay after the hand-off and every later token gap, so
each token's time is known on one clock.
"""
from __future__ import annotations

import queue
import time
from dataclasses import dataclass, field

import numpy as np

import program
from traffic import BLOCK, Traffic


@dataclass
class Rec:
    """One request's fate in the window (host clock, seconds)."""

    spec: object
    due: float
    sent: float = 0.0
    handed: float = 0.0       # submit returned: the program's enqueue
    done: float | None = None
    result: object = None
    error: BaseException | None = None
    future: object = field(default=None, repr=False)

    def token_times(self) -> np.ndarray:
        """When each output token was emitted."""
        first = self.handed + self.result.ttft_s
        return first + np.concatenate(
            [[0.0], np.cumsum(self.result.itl_samples_s)])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def prestore(cluster, traffic: Traffic) -> None:
    """Each document served once, alone, as a one-token request: its
    prefill writes every block of it back to the constellation."""
    if traffic.unique:
        return
    cluster.serve([program.Request(
        prompt=doc, sampling=program.SamplingParams(max_new_tokens=1))
        for doc in traffic.documents])


def warm_restore(cluster, traffic: Traffic) -> None:
    """Each document again, with a question shorter than a block: the
    Get, payload decode and page import at every document size."""
    if traffic.unique:
        return
    cluster.serve([program.Request(
        prompt=doc + traffic.text(BLOCK // 2),
        sampling=program.SamplingParams(max_new_tokens=2))
        for doc in traffic.documents])


def warm_write_back(cluster, traffic: Traffic) -> None:
    """Write-back of every block position a prompt can reach.  The
    payload of a block is computed by an eager forward over the block at
    its offset, so each offset is its own set of shapes.  Documents
    already stored cover the offsets below the longest one; the rest are
    computed here and not stored."""
    n_blocks = traffic.max_prompt_tokens() // BLOCK
    start, past = 0, None
    if not traffic.unique:
        longest = traffic.documents[int(np.argmax(traffic.blocks))]
        toks = program.tokenize(cluster, longest)
        past, cached = cluster.manager.get_cache_tokens(toks)
        start = cached // BLOCK
    else:
        toks = program.tokenize(cluster, "")
    toks = toks + program.tokenize(cluster, traffic.text(
        n_blocks * BLOCK - len(toks) + 1))[1:]
    for j in range(start, n_blocks):
        past = cluster.manager.kvc_fn(toks[:(j + 1) * BLOCK], past,
                                      j * BLOCK)


def warm_programs(cluster, dep: dict) -> None:
    """The executor's programs at every shape the scheduler can launch:
    chunk waves at every power-of-two row count and chunk buffer, the
    mixed step at every buffer, the decode step, and the first-token
    sampler and row reads at every wave size.  Row ``valid`` counts are
    0, so no page changes; idle decode lanes write the scratch page."""
    import jax.numpy as jnp

    from repro.serving.sampler import SamplingParams, stack_sampling

    eng = cluster.engines[0]
    ex, pool = eng.executor, eng.cache
    p, b = pool.pages_per_seq, dep["max_batch"]
    bufs = sorted({ex.chunk_buf(v) for v in range(1, ex.chunk_tokens + 1)})
    for r in program.wave_rows(b):
        for c in bufs:
            z = np.zeros(r, np.int32)
            lg = ex.chunk_wave(np.zeros((r, c), np.int32),
                               np.zeros((r, p), np.int32), z, z)
            for i in range(r):
                lg[i].block_until_ready()
    samp = stack_sampling([SamplingParams()] * b)
    one = stack_sampling([SamplingParams()])
    bt = jnp.asarray(pool.block_tables)
    zb = jnp.zeros(b, jnp.int32)
    for c in bufs:
        ops = (jnp.zeros((1, c), jnp.int32), jnp.zeros((1, p), jnp.int32),
               jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32), *one)
        np.asarray(ex.step(bt, zb, zb, *samp, "greedy", chunk_ops=ops))
    np.asarray(ex.step(bt, zb, zb, *samp, "greedy"))
    row = jnp.zeros(eng.cfg.vocab_size, jnp.dtype(eng.cfg.dtype))
    for n in range(1, b + 1):
        ex.sample_first([row] * n, [SamplingParams()] * n)


def setup(cluster, traffic: Traffic, dep: dict, phase=None) -> None:
    """Every set-up step in turn; ``phase(name)`` is called after each."""
    phase = phase or (lambda name: None)
    for step in (prestore, warm_restore, warm_write_back):
        step(cluster, traffic)
        phase(step.__name__)
    warm_programs(cluster, dep)
    phase("warm_programs")
    cluster.reset_stats()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Timeline:
    """Callbacks due at fixed host times, run from the pacing thread."""

    def __init__(self, events: list[tuple[float, object]]) -> None:
        self.events = sorted(events, key=lambda e: e[0])

    def next_at(self) -> float:
        return self.events[0][0] if self.events else float("inf")

    def run_due(self) -> None:
        while self.events and self.events[0][0] <= time.perf_counter():
            self.events.pop(0)[1]()

    def sleep_until(self, t: float) -> None:
        while True:
            self.run_due()
            now = time.perf_counter()
            if now >= t:
                return
            time.sleep(max(0.0, min(t, self.next_at()) - now))


def _send(cluster, rec: Rec, done_q=None) -> None:
    rec.sent = time.perf_counter()
    fut, _ = cluster.submit(program.request(rec.spec))
    rec.handed = time.perf_counter()
    rec.future = fut

    def finished(f, rec=rec):
        rec.done = time.perf_counter()
        if done_q is not None:
            done_q.put(rec)

    fut.add_done_callback(finished)


def open_loop(cluster, specs, t0: float, seconds: float,
              timeline: Timeline) -> list[Rec]:
    """Every request handed over at its due time, whatever the backlog."""
    recs = []
    for spec in specs:
        rec = Rec(spec=spec, due=t0 + spec.due_s)
        timeline.sleep_until(rec.due)
        _send(cluster, rec)
        recs.append(rec)
    timeline.sleep_until(t0 + seconds)
    timeline.run_due()
    return recs


def closed_loop(cluster, traffic: Traffic, t0: float, seconds: float,
                timeline: Timeline) -> list[Rec]:
    """``clients`` callers, each sending its next request the moment its
    last one finished, until the window closes."""
    done_q: queue.Queue = queue.Queue()
    recs = []
    end = t0 + seconds
    timeline.sleep_until(t0)
    for _ in range(traffic.cfg["clients"]):
        rec = Rec(spec=traffic.next_closed(), due=t0)
        _send(cluster, rec, done_q)
        recs.append(rec)
    while True:
        timeline.run_due()
        now = time.perf_counter()
        if now >= end:
            break
        try:
            prev = done_q.get(timeout=max(0.0, min(end, timeline.next_at())
                                          - now))
        except queue.Empty:
            continue
        if time.perf_counter() < end:
            rec = Rec(spec=traffic.next_closed(), due=prev.done)
            _send(cluster, rec, done_q)
            recs.append(rec)
    timeline.run_due()
    return recs


def collect(recs: list[Rec], deadline: float) -> None:
    """Wait for every request until ``deadline``; one that has not
    finished by then, or raised, is failed."""
    for rec in recs:
        try:
            rec.result = rec.future.result(
                timeout=max(0.0, deadline - time.perf_counter()))
        except Exception as e:    # timed out, or failed inside the program
            rec.error = e
