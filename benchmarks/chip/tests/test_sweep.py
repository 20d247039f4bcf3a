"""The rate sweep at smoke widths on the CPU: one set-up, a window per
rate, and the rule that decides whether a rate is sustained."""
import smoke
import sweep


def test_sustained_rule():
    assert sweep.sustained(1.0, 2.5)
    assert not sweep.sustained(1.0, 2.6)
    assert sweep.sustained(0.1, 0.7)


def test_sweep_windows_share_one_setup():
    c = smoke.cell("rag-open")
    rows = list(sweep.sweep(c, 2 ** 32 + 7, 2.0, [1.0, 2.0],
                            require_tpu=False))
    assert [r["rate_rps"] for r in rows] == [1.0, 2.0][:len(rows)]
    assert rows[0]["attempted"] == 2 and rows[0]["failed"] == 0
    assert rows[0]["prefix_hit_rate"] > 0
    assert c.traffic["rate_rps"] == smoke.traffic("rag-open")["rate_rps"]
