"""The readers of the program's own counters and request stamps, on a
hand-made ``RunData``: each value as defined, and silence where the
program counted nothing or, as an older program, has no such counter."""
from types import SimpleNamespace

import numpy as np
import pytest

import smoke
import harness
import fabric_get_ms
import queue_wait_p95_s
import restore_wait_share
import round_host_ms
import write_back_wait_share

STATS = {"rounds": 40, "round_s": 5.0, "step_sync_s": 3.0,
         "write_back_wait_s": 1.5, "restore_wait_s": 0.25,
         "fabric_gets": 4, "fabric_get_s": 0.8}


def rec(handed, wait):
    res = SimpleNamespace() if wait is None else SimpleNamespace(
        queue_wait_s=wait)
    return SimpleNamespace(handed=handed, result=res)


def run(stats=STATS, recs=()):
    return harness.RunData(cfg=smoke.CONFIG, peak={}, recs=list(recs),
                           t0=100.0, t_end=150.0, setup_s=1.0,
                           stats=dict(stats), window_compiles=0)


def test_counter_readers():
    r = run()
    assert round_host_ms.read(r) == pytest.approx(1e3 * 2.0 / 40)
    assert write_back_wait_share.read(r) == pytest.approx(1.5 / 50)
    assert restore_wait_share.read(r) == pytest.approx(0.25 / 50)
    assert fabric_get_ms.read(r) == pytest.approx(200.0)


@pytest.mark.parametrize("reader", [round_host_ms, fabric_get_ms])
def test_zero_counts_are_silent(reader):
    assert reader.read(run(dict(STATS, rounds=0, fabric_gets=0))) is None


@pytest.mark.parametrize("reader", [round_host_ms, write_back_wait_share,
                                    restore_wait_share, fabric_get_ms,
                                    queue_wait_p95_s])
def test_older_program_is_silent(reader):
    older = {k: v for k, v in STATS.items() if k not in {
        "rounds", "round_s", "step_sync_s", "write_back_wait_s",
        "restore_wait_s", "fabric_gets", "fabric_get_s"}}
    assert reader.read(run(older, [rec(101.0, None)] * 3)) is None


def test_queue_wait_over_requests_admitted_in_the_window():
    recs = [rec(90.0, 5.0),      # admitted before the window opened
            rec(99.0, 2.0),      # admitted at its opening
            rec(120.0, 0.5), rec(130.0, 1.0), rec(140.0, 3.0),
            rec(148.0, 4.0),     # admitted after the window closed
            rec(149.0, 0.0)]
    got = queue_wait_p95_s.read(run(recs=recs))
    assert got == pytest.approx(float(np.percentile(
        [2.0, 0.5, 1.0, 3.0, 0.0], 95)))
    assert queue_wait_p95_s.read(run(recs=recs[:1])) is None
