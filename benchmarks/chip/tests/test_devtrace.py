"""The reduction from a trace to the per-layer metrics, on a small trace
written out by hand in the plain form ``devtrace.load`` produces."""
import gzip
import json
from types import SimpleNamespace

import pytest

import smoke
import devtrace
import chunked_prefill_paged_roofline
import device_idle_share
import step_ms

MS = 1_000_000
TRACE = {
    "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit__paged_step(3)", 0, 4 * MS],
                        ["jit__mixed_step(7)", 10 * MS, 6 * MS]],
        "XLA Ops": [["chunked_prefill_paged", 1 * MS, 1 * MS],
                    ["chunked_prefill_paged", 2 * MS, 1 * MS],
                    ["fusion.12", 3 * MS, 1 * MS],
                    ["chunked_prefill_paged", 10 * MS, 2 * MS],
                    ["chunked_prefill_paged", 12 * MS, 2 * MS],
                    ["fusion.12", 12 * MS, 3 * MS]]}},
    "spans": [["bench.trace_window", 0, 20 * MS],
              ["write_back", 4 * MS, 5 * MS],
              ["lookup", 16 * MS, 1 * MS]],
}


def test_busy_is_the_union_of_operations():
    assert devtrace.busy_s(TRACE, 0, 20 * MS) == pytest.approx(8e-3)
    assert devtrace.busy_s(TRACE, 11 * MS, 13 * MS) == pytest.approx(2e-3)
    assert devtrace.busy_s({"devices": {}, "spans": []}, 0, 1) is None


def test_idle_gaps_named_by_host_spans():
    gaps = devtrace.idle_gaps(TRACE, 0, 20 * MS)
    assert gaps[0] == ["write_back", pytest.approx(6e-3)]
    assert ["lookup", pytest.approx(5e-3)] in gaps
    assert ["none", pytest.approx(1e-3)] in gaps


def test_top_ops_and_device_seconds():
    assert devtrace.top_ops(TRACE)[0] == ["chunked_prefill_paged",
                                          pytest.approx(6e-3)]
    s, n = devtrace.device_seconds(TRACE, devtrace.MODULES, "_mixed_step")
    assert (s, n) == (pytest.approx(6e-3), 1)


def test_round_trip(tmp_path):
    devtrace.save(TRACE, tmp_path / "t.json.gz")
    assert devtrace.read(tmp_path / "t.json.gz") == json.loads(
        json.dumps(TRACE))


def _run(**kw):
    base = dict(cfg=smoke.CONFIG, trace=TRACE, busy_s=8e-3, trace_s=20e-3,
                kernel_calls=[],
                peak={"bf16_flops": 197e12, "hbm_bytes_s": 819e9})
    base.update(kw)
    return SimpleNamespace(**base)


def test_step_and_idle_readers():
    assert step_ms.read(_run()) == pytest.approx(5.0)
    assert device_idle_share.read(_run()) == pytest.approx(0.6)
    assert step_ms.read(_run(trace=None)) is None


def test_roofline_reader_scales_to_the_traced_calls():
    import flops

    # two recorded calls, two layers each: four kernel events
    calls = [([100, 7], [1, 1]), ([0], [256])]
    least = sum(flops.least_seconds(*flops.paged_call(smoke.CONFIG, o, v),
                                    _run().peak) for o, v in calls)
    got = chunked_prefill_paged_roofline.read(_run(kernel_calls=calls))
    assert got == pytest.approx(100 * least / 6e-3)
    # a call recorded on the host whose events fell outside the trace
    got3 = chunked_prefill_paged_roofline.read(
        _run(kernel_calls=calls + [([5], [1])]))
    assert got3 < got
    assert chunked_prefill_paged_roofline.read(_run(kernel_calls=[])) is None


def test_load_keeps_the_annotated_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("step"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = devtrace.load(str(tmp_path), {"step", devtrace.WINDOW_SPAN})
    names = [s[0] for s in t["spans"]]
    assert names.count("step") == 1 and names.count(devtrace.WINDOW_SPAN) == 1
    (win,) = [s for s in t["spans"] if s[0] == devtrace.WINDOW_SPAN]
    (step,) = [s for s in t["spans"] if s[0] == "step"]
    assert win[1] <= step[1] and step[1] + step[2] <= win[1] + win[2]
