"""Readings for the limit of ``correct``: the program's widest greedy gap
and the float8 control's, over many seeds in one process.

    python benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed: a run's set-up and a window of ``--seconds`` at the cell's
own load, then the sample of finished requests that a run checks, read
twice against the float32 reference: the served tokens (the program's
reading) and, at each position of the same prompts and tokens, the token
that the reference computed from float8 operands puts first (the
control's reading).  Both readings go through the run's own verdict
(``harness.verdict``), the control's tokens in the served tokens' place,
so a sound control line shows ``correct`` true and ``control_correct``
false.  One JSON line per seed on standard output.  The benchmark's own
runs never compute the control.  Each seed's constellation holds its
documents' payloads in host memory (about 14 GB for the RAG cell), and
a seed's state is not all given back before the next: on a machine of
40 GiB, give each seed a process of its own.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness

    harness.use_compile_cache(ROOT)
    c = harness.cell(args.workload, False)
    for seed in args.seeds:
        print(json.dumps(reading(c, seed, args.seconds)), flush=True)
    return 0


def reading(c, seed: int, seconds: float, require_tpu: bool = True) -> dict:
    import harness

    t = time.perf_counter()
    data, device, cluster = harness.serve_window(
        c, seed, seconds, False, t, require_tpu)
    cluster.stop_workers(drain=False)
    harness.free(cluster)
    del cluster
    numbers = harness.check(c, seed, data, control=True)
    ok, _ = harness.verdict(c, data, numbers)
    control_ok, _ = harness.verdict(
        c, data, dict(numbers, max_gap_std=numbers["control_max_gap_std"]))
    return {"seed": seed, "device": device["kind"],
            "failed": sum(harness.failed(r) for r in data.recs),
            "attempted": len(data.recs), "correct": ok,
            "control_correct": control_ok, **numbers}


if __name__ == "__main__":
    sys.exit(main())
