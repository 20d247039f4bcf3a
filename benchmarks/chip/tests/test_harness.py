"""The harness end to end on the CPU at smoke widths, and its refusals.

A run here drives the real program (router, engine, page pool, chunked
prefill, constellation Get/Set) with the Pallas kernels in interpret
mode; only the look for a chip is skipped.  The faults break the timed
path underneath and must turn ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import smoke
import control
import harness

ROOT = Path(__file__).resolve().parents[3]
SEED = 2 ** 32 + 99


def run(name, trace=False, seconds=3.0):
    c = smoke.cell(name, trace)
    return harness.run(c, SEED, seconds, trace, time.perf_counter(),
                       require_tpu=False)


def test_smoke_run_in_interpret_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    r = run("rag-open")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 6
    assert set(r["metrics"]) == {"ttft_p95_s", "itl_p95_s", "setup_s"}
    assert r["checks"]["restored_checked"]["value"] >= 1
    assert list(r)[-1] == "checks"


def test_closed_loop_per_layer_metrics():
    r = run("unique-batch", trace=True)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["prefix_hit_rate"]["value"] == 0
    assert m["batch_occupancy"]["value"] > 0
    # the CPU has no device trace: the trace's metrics stay silent
    assert "step_ms" not in m and "chunked_prefill_paged_roofline" not in m


def _altered_tokens(monkeypatch):
    from repro.serving.executor import PagedExecutor

    step = PagedExecutor.step

    def wrong(self, *a, **k):
        return (step(self, *a, **k) + 1) % self.cfg.vocab_size

    monkeypatch.setattr(PagedExecutor, "step", wrong)


def _page_dropped(monkeypatch):
    from repro.serving.skycache import SkyKVCAdapter

    to_pages = SkyKVCAdapter.payload_to_pages

    def dropped(self, *a, **k):
        k_b, v_b = to_pages(self, *a, **k)
        return k_b.at[:, -1].set(0), v_b.at[:, -1].set(0)

    monkeypatch.setattr(SkyKVCAdapter, "payload_to_pages", dropped)


@pytest.mark.parametrize("fault", [_altered_tokens, _page_dropped])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = run("rag-open")
    assert not r["correct"]
    gap = r["checks"]["max_gap_std"]
    assert gap["value"] > gap["limit"]


def test_control_reads_above_the_program():
    """The control at a size a test can hold: four layers of width 512,
    outputs of about 48 tokens, so that the greedy path has near-ties for
    float8 rounding to break.  The limit is this size's own, between the
    program's reading here (about 0.015 std) and the control's (0.15 std
    and more)."""
    cfg = dict(smoke.CONFIG, hidden_size=512, intermediate_size=1024,
               num_hidden_layers=4, vocab_size=2048, num_attention_heads=8,
               num_key_value_heads=4)
    c = smoke.cell("rag-open", config=cfg)
    c.config["deployment"]["check_rows"] = 64
    c.traffic["output_tokens"] = {"median": 48, "sigma": 0.3, "min": 32,
                                  "max": 64}
    c.traffic["check"] = dict(c.traffic["check"], max_gap_std=0.06)
    r = control.reading(c, SEED, 3.0, require_tpu=False)
    assert r["failed"] == 0
    assert r["control_max_gap_std"] > 0.06 > 3 * r["max_gap_std"]
    assert r["correct"] and not r["control_correct"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "internlm2-1.8b.rag-open", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
