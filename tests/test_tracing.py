"""Spans and counters inside the serving loop.

The program's own instrumentation: ``repro.trace.span`` annotations on
the profiler's host plane (one line per thread, request spans tagged
with the request id) and the ``EngineStats`` counters taken at the same
boundaries.  Both are checked on a real engine at smoke widths: the
spans in a profiler trace read back through ``jax.profiler.ProfileData``,
the counters on a closed batch whose prompts share a prefix.
"""
import warnings
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, smoke_config
from repro.core import (
    ConstellationKVC,
    ConstellationSpec,
    LosWindow,
    Sat,
    Strategy,
)
from repro.models.model import Model
from repro.serving import Engine, Request, SamplingParams

DOC = "SkyMemory stripes KV cache chunks across LEO satellites. " * 2


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(get_config("internlm2-1.8b")).replace(dtype="float32")
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def engine(setup, max_batch=2):
    model, params = setup
    kvc = ConstellationKVC(
        ConstellationSpec(15, 15, 550.0), LosWindow(Sat(7, 7), 9, 9),
        Strategy.ROTATION_HOP, num_servers=10, chunk_bytes=6 * 1024)
    return Engine(model, params, kvc=kvc, block_size=16, max_seq_len=256,
                  max_batch=max_batch)


def requests(questions, max_new=4, doc=DOC):
    sp = SamplingParams(max_new_tokens=max_new)
    return [Request(prompt=doc + q, sampling=sp) for q in questions]


def host_events(trace_dir):
    """``{line: [(name, start_ns, end_ns, stats)]}`` of the newest
    trace's host plane, a line per thread (the lines' names are the
    process's, so they are told apart by their place in the plane)."""
    from jax.profiler import ProfileData

    path = max(Path(trace_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    out = {}
    with warnings.catch_warnings():
        # the stats' C++ type warns on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                out.setdefault((plane.name, i), []).extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events)
    return out


def test_spans_on_the_host_plane(setup, tmp_path):
    eng = engine(setup)
    # the same shapes outside the trace, over a document of its own
    eng.generate(requests(["warm a?", "warm b?"], doc=DOC.upper()))
    reqs = requests(["first question?", "second question?"])
    with jax.profiler.trace(str(tmp_path)):
        res = eng.generate(reqs)
    assert res[1].cached_tokens > 0       # the second restored the first's
    lines = host_events(tmp_path)
    names = {ev[0] for evs in lines.values() for ev in evs}
    for want in ("sched.round", "fabric.get", "kv.page_import", "exec.sync",
                 "restore.decode", "write_back.forward"):
        assert want in names, want

    # every step's sync lies inside a scheduling round of the same thread
    syncs = [(line, s, e) for line, evs in lines.items()
             for name, s, e, _ in evs if name == "exec.sync"]
    assert syncs
    for line, s, e in syncs:
        assert any(name == "sched.round" and rs <= s and e <= re
                   for name, rs, re, _ in lines[line])

    # the fetch-ahead worker's spans are on a thread of their own
    fetch = [k for k, evs in lines.items()
             if any(ev[0] == "restore.decode" for ev in evs)]
    assert fetch and not any(ev[0] == "sched.round"
                             for k in fetch for ev in lines[k])

    # a request's spans carry its id, on every thread they run on
    rid = {ev[0]: ev[3].get("rid") for evs in lines.values() for ev in evs
           if ev[0] in ("kv.restore_wait", "kv.page_import",
                        "restore.decode")}
    assert set(rid.values()) == {reqs[1].request_id}
    wb = {ev[3].get("rid") for evs in lines.values() for ev in evs
          if ev[0] == "write_back.blocks"}
    assert reqs[0].request_id in wb


def test_counters_on_a_closed_batch(setup):
    eng = engine(setup)
    questions = [f"question {i}: what is cached?" for i in range(5)]
    reqs = requests(questions, max_new=6)
    assert all(len(eng.tokenizer.encode(r.prompt)) >= eng.block_size
               for r in reqs)
    res = eng.generate(reqs)
    st = eng.stats
    # every prompt is a block or longer, so each request was looked up
    # in the constellation once (no preemption restores it again)
    assert st.fabric_gets == len(reqs)
    assert st.fabric_get_s > 0
    assert sum(r.cached_tokens > 0 for r in res) >= len(reqs) - 1
    assert st.rounds > 0
    assert 0 < st.step_sync_s <= st.round_s
    assert st.write_back_wait_s >= 0 and st.restore_wait_s >= 0
    assert st.mixed_steps <= st.decode_steps
    assert st.chunk_waves > 0
    for r in res:
        assert 0 <= r.queue_wait_s <= r.ttft_s
    # five requests on two slots: the later ones queued for a slot
    assert max(r.queue_wait_s for r in res) > 0
