"""Benchmark harness: one module per paper table/figure.

  bench_hadamard        -- Figs 4-7 + Appendix B (in-place) + C (bf16)
  bench_quant_accuracy  -- section 4.2 MMLU table (container-scale proxy)
  bench_e2e_overhead    -- section 1 rotation-overhead motivation
  bench_fused_quant     -- conclusion's future-work fusion (beyond paper)
  bench_quant_dot       -- fused rotate+quantize+GEMM consumer (PR 3)
  bench_serve_prequant  -- pre-quantized QTensor weights vs per-forward
                           weight quantization on the serving path (PR 4)
  bench_serve_loop      -- continuous-batching engine under a synthetic
                           arrival stream: tok/s, occupancy, p50/p99
                           per-token latency (PR 6)

Prints ``name,key=value,...`` CSV lines; ``--only <name>`` runs a subset.
``--json PATH`` additionally writes machine-readable records
``{bench, shape, dtype, backend, ms, gbps}`` -- the perf-trajectory
format (``BENCH_<tag>.json`` files are committed per PR so regressions
are diffable across the stack's history; ``benchmarks/compare.py``
diffs two of them record-by-record and exits nonzero on ms regressions
-- the CI bench-smoke job runs it against the committed baseline).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few steps: CI guard that the perf "
                         "scripts still run, not a measurement")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable perf records "
                         "({bench, shape, dtype, backend, ms, gbps}) to PATH")
    args = ap.parse_args()

    from repro.launch.env import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (  # noqa: PLC0415
        bench_e2e_overhead,
        bench_fused_quant,
        bench_hadamard,
        bench_quant_accuracy,
        bench_quant_dot,
        bench_serve_loop,
        bench_serve_prequant,
    )

    suites = {
        "hadamard": bench_hadamard.run,
        "quant_accuracy": bench_quant_accuracy.run,
        "e2e_overhead": bench_e2e_overhead.run,
        "fused_quant": bench_fused_quant.run,
        "quant_dot": bench_quant_dot.run,
        "serve_prequant": bench_serve_prequant.run,
        "serve_loop": bench_serve_loop.run,
    }
    csv, records = [], []
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        print(f"# running {name} ...", file=sys.stderr)
        fn(csv, smoke=args.smoke, records=records)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    for line in csv:
        print(line)
    if args.json is not None:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} perf records to {args.json}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
