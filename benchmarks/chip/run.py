"""Run one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json`` at the checkout's
root.  The run needs the TPU chips the cell asks for: without them it
exits with code 3 and prints no result.  Set-up, the window and the check
are described in ``harness.py``; the last line of standard output is the
result object, and the numbers compared with their limits are the last
lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness

    try:
        c = harness.cell(args.workload, bool(args.trace))
        harness.use_compile_cache(ROOT)
        result = harness.run(c, args.seed, args.seconds, bool(args.trace),
                             T_START)
    except (harness.Refused, FileNotFoundError) as e:
        print(f"run.py: refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
