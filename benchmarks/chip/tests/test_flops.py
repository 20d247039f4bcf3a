"""The operation and byte counts against brute-force counts."""
import numpy as np

import smoke
import flops
from reference import dims


def test_paged_call_counts_each_query_and_key():
    cfg = smoke.CONFIG
    z = dims(cfg)
    offsets, valid = [0, 130, 7], [5, 1, 0]
    keys = sum(off + i + 1 for off, v in zip(offsets, valid)
               for i in range(v))
    f, b = flops.paged_call(cfg, offsets, valid)
    assert f == 4 * z["h"] * z["hd"] * keys
    kv = sum(off + v for off, v in zip(offsets, valid))
    assert b == 2 * 2 * kv * z["hkv"] * z["hd"] + 2 * 2 * sum(valid) * \
        z["h"] * z["hd"]


def test_token_flops_by_hand():
    cfg = smoke.CONFIG
    d, h, hkv, hd, ff, n, v = 256, 4, 2, 64, 512, 2, 512
    body = n * (d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * ff)
    pos = np.array([0, 9])
    want = sum(2 * body + 4 * n * h * hd * (p + 1) for p in pos) \
        + 2 * d * v
    assert flops.token_flops(cfg, pos, np.array([False, True])) == want


def test_least_seconds_takes_the_binding_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert flops.least_seconds(1000.0, 5.0, peak) == 10.0
    assert flops.least_seconds(10.0, 50.0, peak) == 5.0
