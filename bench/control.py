"""The control of the correctness check: the plain reference put in the
program's place one precision step down (int4 where the configuration
states fp8 and int8), read with the same number the check compares.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 30]

For each seed, in one process: the cell's open loop for ``--seconds`` at
its own load, the same sample of finished requests that a run checks,
and two readings over those prompts and served tokens. ``program`` is
the widest gap below the float32 reference's best logit of a served
token (what a run compares). ``control`` is the widest gap of the token
that the int4 reference puts first at each of the same positions. A
limit must lie above the program's readings and below the control's.
The benchmark's runs never run this."""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_gaps(config: dict, seed: int, picked, blk):
    """(program gaps, control gaps) over the served tokens of
    ``picked``, the reference run in blocks of ``blk``."""
    import numpy as np

    from bench import spec

    ref = spec.load_reference(config["reference"])
    outs = [np.asarray(r.tokens, np.int32) for r in picked]
    seqs, pos = ref.served_sequences([r.prompt for r in picked], outs)
    ctrl = ref.argmax_at(config, seed, seqs, pos, blk, precision="int4")
    return ref.gaps_at(config, seed, seqs, pos,
                       [np.concatenate(outs), ctrl], blk)


def read_seed(cell, seed: int, seconds: float, devices) -> dict:
    from bench import check, loop, serve, traffic
    from repro.launch.mesh import make_local_mesh

    mix = cell.traffic
    cfg = serve.model_config(cell.config)
    mesh = make_local_mesh(1, devices[:1])
    engine = serve.make_engine(
        cfg, serve.make_params(cfg, cell.config, seed, mesh), mesh, mix)
    engine.warmup()
    loop.warm(engine)
    planned = traffic.generate(mix, cell.config["vocab_size"], seed, seconds)
    log = loop.drive(engine, planned, seconds, mix["window"],
                     drain_limit_s=float(mix.get("drain_limit_s", 0.0)))
    del engine
    gc.collect()
    blk = check.block(mix)
    picked = check.sample(log.records, blk[0], seed)
    prog, ctrl = control_gaps(cell.config, seed, picked, blk)
    return {"seed": seed, "requests": len(picked), "tokens": len(prog),
            "program": float(prog.max()), "control": float(ctrl.max()),
            "control_top1_differs": float((ctrl > 0).mean())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    from repro.launch.env import enable_compile_cache

    cell = spec.Cell(spec.load_benchmark(), args.workload)
    devices = harness.require_chips(cell.chips)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(cell, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
