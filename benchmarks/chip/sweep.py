"""Find the highest open-loop rate a cell sustains: one set-up, then a
window at each rate in turn.

    python benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates <r> [<r> ...]

A rate is sustained when every request finished and the requests due in
the last third of the window waited for their first token no more than
twice as long as those of the first third, plus half a second: a queue
that grows all through the window fails that.  The sweep stops at the
first rate that is not sustained.  One JSON line per rate on standard
output.  The benchmark's own runs never sweep: the rate a cell offers is
a number in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def sustained(first: float, last: float) -> bool:
    return last <= 2 * first + 0.5


def summary(data, rate: float) -> dict:
    from common import percentile, ttft_sample

    import harness

    ttft = ttft_sample(data)
    thirds = np.array_split(ttft[np.argsort([r.due for r in data.recs])],
                            min(3, len(ttft)))
    first, last = (float(np.median(t)) for t in (thirds[0], thirds[-1]))
    failed = sum(harness.failed(r) for r in data.recs)
    out = {"rate_rps": rate, "attempted": len(data.recs), "failed": failed,
           "ttft_p50_s": percentile(ttft, 50),
           "ttft_p95_s": percentile(ttft, 95),
           "ttft_first_third_p50_s": first, "ttft_last_third_p50_s": last}
    for name in ("itl_p95_s", "tokens_per_s", "prefix_hit_rate"):
        out[name] = __import__(name).read(data)
    out["sustained"] = failed == 0 and sustained(first, last)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness

    harness.use_compile_cache(ROOT)
    c = harness.cell(args.workload, False)
    if c.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    for row in sweep(c, args.seed, args.seconds, args.rates):
        print(json.dumps(row), flush=True)
    return 0


def sweep(c, seed: int, seconds: float, rates: list[float],
          require_tpu: bool = True):
    """One set-up, then a window at each rate until one is not
    sustained; yields each window's summary."""
    import harness

    sv = harness.prepare(c, seed, require_tpu)
    base = c.traffic
    try:
        for i, rate in enumerate(rates):
            # the stored documents stay; each window draws fresh questions
            c.traffic = sv.traffic.cfg = dict(base, rate_rps=rate)
            sv.traffic.rng = np.random.default_rng(
                np.random.SeedSequence([int(seed), 0x5EE9, i]))
            t0 = time.perf_counter() + 0.05
            row = summary(harness.window(sv, seconds, False, t0, 0.0), rate)
            yield row
            if not row["sustained"]:
                break
    finally:
        c.traffic = base
        sv.cluster.stop_workers(drain=False)


if __name__ == "__main__":
    sys.exit(main())
