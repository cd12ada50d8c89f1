"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints what it did on earlier lines, each number the correctness check
compared beside its limit as the last lines of standard error, and one
JSON object as the last line of standard output. Exits non-zero, with
no result, where JAX finds no TPU or fewer chips than the cell needs,
and where the checkout's ``src/`` is missing."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _finite(x):
    """JSON has no infinity: a gap that reads infinite prints as 1e300."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec

    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the system under test is missing: {e}",
              file=sys.stderr)
        return 2
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = _finite(result)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
