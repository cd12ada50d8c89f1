"""Find a cell's knee: the open loop at several rates, one process, the
weights made once, a fresh engine per rate.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 20 --seed <n>

Each rate runs the cell's mix with its arrivals at that rate, no initial
burst and the window open from the start, and prints one line: offered
and admitted requests per second, output tokens per second, the p50 and
p90 of the wait to admission and of the time to first token, and the
requests due in the window still queued at its close. The knee is the
highest rate whose queue stays bounded: admitted keeps up with offered
and the waits do not grow with the window."""
import argparse
import copy
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness, loop, serve, spec, traffic
    from repro.launch.env import enable_compile_cache
    from repro.launch.mesh import make_local_mesh

    cell = spec.Cell(spec.load_benchmark(), args.workload)
    devices = harness.require_chips(cell.chips)
    enable_compile_cache()
    cfg = serve.model_config(cell.config)
    mesh = make_local_mesh(1, devices[:1])
    params = serve.make_params(cfg, cell.config, args.seed, mesh)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"].update(rate_rps=rate, initial_burst=0)
        engine = serve.make_engine(cfg, params, mesh, mix)
        engine.warmup()
        loop.warm(engine)
        planned = traffic.generate(mix, cell.config["vocab_size"],
                                   args.seed, args.seconds)
        log = loop.drive(engine, planned, args.seconds, {"opens": "start"})
        run = harness.Run(cell, log, 0.0, None, None, args.seconds)
        due = run.due_in_window()
        q, f = run.queue_waits_s(), run.first_token_waits_s()

        def p(values, pct, scale=1.0):
            v = harness.percentile(values, pct)
            return None if v is None else v * scale

        print(json.dumps({
            "rate_rps": rate,
            "offered_rps": len(due) / args.seconds,
            "admitted_rps": len(run.admits()) / args.seconds,
            "output_tok_s": run.tokens_in_window() / args.seconds,
            "queue_wait_p50_s": p(q, 50), "queue_wait_p90_s": p(q, 90),
            "ttft_p50_s": p(f, 50), "ttft_p90_s": p(f, 90),
            "decode_step_p50_ms": p([b - a for a, b, _ in run.decodes()],
                                    50, 1e3),
            "admit_p50_ms": p([b - a for a, b, _ in run.admits()], 50, 1e3),
            "queued_at_close": sum(1 for r in due if r.admit is None)}),
            flush=True)
        del engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
