"""Continuous-batching serving launcher: admit and retire requests
mid-decode over pre-quantized QTensor weights (the production serving
loop from the ROADMAP; subsystem in ``repro.serving``).

    PYTHONPATH=src python -m repro.launch.serve_loop --arch llama3-8b \
        --scale 0.02 --slots 8 --max-len 192 --prefill-len 64 \
        --requests 32 --rate 0.5 --quant fp8_e4m3 --rotate hadamard

Serves a seeded Poisson arrival stream (mixed prompt/generation
lengths) and reports tokens/s, slot occupancy, p50/p99 per-token
latency, and the admission/retirement/stall counters. All jit compiles
are paid in a warm-up step before the first request, so the reported
latencies are steady-state.

``REPRO_ABFT=1`` serves checksum-VERIFIED steps (silent-data-corruption
detection; ``repro.verify``, DESIGN.md section 14); the run's health
counters -- guard/ABFT trips, degradations, SDC retirements -- print as
the structured ``health`` line of the summary.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.launch.env import enable_compile_cache, harden_host_env
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_param_init, param_shardings
from repro.launch.train import scaled_config
from repro.serving import ServeEngine, synthetic_stream


def build_engine(args, cfg=None, mesh=None):
    """Config -> (engine, cfg): shared by the CLI, the bench suite and
    ``chip_smoke.py``. ``mesh`` defaults to ``make_local_mesh(args.mp)``
    over every device."""
    if cfg is None:
        quant = QuantConfig(mode=args.quant, rotate=args.rotate,
                            backend=args.kernel,
                            kv_quant=args.quant != "none")
        cfg = scaled_config(get_config(args.arch),
                            args.scale).with_quant(quant)
        prequant = (args.quant != "none" if args.prequant is None
                    else args.prequant)
        if prequant:
            cfg = dataclasses.replace(cfg, weight_quant="int8")
    if mesh is None:
        mesh = make_local_mesh(args.mp)
    with mesh:
        ps = param_shardings(cfg, mesh)
        params = jax.jit(make_param_init(cfg), out_shardings=ps)(
            jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, mesh, num_slots=args.slots,
                         max_len=args.max_len,
                         prefill_len=args.prefill_len,
                         eos_id=args.eos_id,
                         max_queue=getattr(args, "max_queue", None),
                         watchdog_ms=getattr(args, "watchdog_ms", None))
    return engine, cfg


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=192)
    ap.add_argument("--prefill-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step (Poisson)")
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=0,
                    help="0 = prefill-len")
    ap.add_argument("--gen-min", type=int, default=8)
    ap.add_argument("--gen-max", type=int, default=32)
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8_e4m3", "fp8_e5m2"])
    ap.add_argument("--rotate", default="none", choices=["none", "hadamard"])
    ap.add_argument("--kernel", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--prequant", dest="prequant", action="store_true",
                    default=None,
                    help="pre-quantize weights ONCE at load into QTensors; "
                         "default: on whenever --quant is not 'none'")
    ap.add_argument("--no-prequant", dest="prequant", action="store_false")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: submits beyond this "
                         "depth are rejected immediately (backpressure)")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="per-request TTL = arrival + max_new_tokens + "
                         "slack steps; expired queued requests are shed, "
                         "expired in-flight slots retired as timed_out")
    ap.add_argument("--watchdog-ms", type=float, default=None,
                    help="decode-step wall-clock bound; two consecutive "
                         "trips degrade the engine one ladder rung")
    return ap.parse_args(argv)


def make_stream(args, cfg):
    """The seeded arrival stream the arguments describe."""
    return synthetic_stream(
        args.requests, vocab_size=cfg.vocab_size,
        prompt_len=(args.prompt_min, args.prompt_max or args.prefill_len),
        max_new_tokens=(args.gen_min, args.gen_max),
        rate=args.rate, seed=args.seed,
        deadline_slack=args.deadline_slack)


def report(engine):
    """Print the run's summary lines; returns ``engine.summary()``."""
    s = engine.summary()
    print(f"served {s['requests']:.0f} requests / "
          f"{s['generated_tokens']:.0f} tokens in "
          f"{s['decode_steps']:.0f} decode steps "
          f"({s['idle_steps']:.0f} idle)")
    print(f"throughput: {s['tokens_per_s']:.1f} tok/s, "
          f"occupancy {s['occupancy'] * 100:.0f}%, per-token latency "
          f"p50 {s['p50_token_ms']:.1f} ms / p99 {s['p99_token_ms']:.1f} ms")
    print(f"scheduler: admitted={s.get('admitted', 0):.0f} "
          f"retired={s.get('retired', 0):.0f} "
          f"prefill_inserts={s.get('prefill_inserts', 0):.0f} "
          f"queue_full_stalls={s.get('queue_full_stalls', 0):.0f}")
    print(f"robustness: ok={s.get('status_ok', 0):.0f} "
          f"timed_out={s.get('status_timed_out', 0):.0f} "
          f"rejected={s.get('status_rejected', 0):.0f} "
          f"degraded={s.get('status_degraded', 0):.0f} "
          f"(shed={s.get('shed', 0):.0f} watchdog_trips="
          f"{s.get('watchdog_trips', 0):.0f} degrades="
          f"{s.get('degrades', 0):.0f} rung={s.get('rung', 0):.0f} "
          f"guards={'on' if s.get('guards_enabled') else 'off'})")
    h = s["health"]
    print("health: " + " ".join(f"{k}={v}" for k, v in h.items()))
    print(f"invariants: decode_executables={s['decode_executables']:.0f} "
          f"(constant across admissions/retirements), "
          f"quantize_weight_calls={s['quantize_weight_calls']:.0f} "
          f"during serve")
    return s


def main(argv=None):
    harden_host_env()                 # flags only; re-exec is __main__'s
    enable_compile_cache()
    args = parse_args(argv)
    engine, cfg = build_engine(args)
    if cfg.weight_quant == "int8":
        print("weights pre-quantized once at load (QTensor tree; "
              f"consumer mode={cfg.quant.mode})")
    t_compile = engine.warmup()
    print(f"warmup: prefill/insert/decode compiled in {t_compile:.2f}s")
    engine.run(make_stream(args, cfg))
    report(engine)
    return engine


if __name__ == "__main__":
    harden_host_env(reexec=True)
    main()
