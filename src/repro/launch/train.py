"""Fault-tolerant training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --shape train_4k --steps 200 --scale 0.02 --quant fp8_e4m3 \
        --rotate hadamard --ckpt-dir /tmp/ckpt

Fault-tolerance story (designed for 1000+-node fleets, exercised here on
one host -- every mechanism is the single-controller JAX pattern):

  * checkpoint/restart: async sharded checkpoints every --ckpt-every
    steps; on launch the newest valid checkpoint is restored and the data
    pipeline (stateless, step-keyed) resumes bit-identically.
  * preemption: SIGTERM/SIGINT triggers a synchronous final checkpoint
    before exit (the TPU preemption-notice pattern).
  * node failure: on a real fleet the controller re-schedules and restarts
    from the last checkpoint -- identical code path to restart, which is
    what this launcher tests.
  * elastic rescaling: checkpoints are mesh-agnostic; --mp can differ
    between runs and restore re-shards (tests cover a mesh change).
  * straggler mitigation: per-step wall-clock is tracked; steps slower
    than --straggler-z sigma above the running mean are logged with the
    step's device set so a fleet scheduler can quarantine hosts. (With
    one host this is observability-only, as real detection needs per-host
    timing telemetry.)
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.checkpoint.store import wait_for_writes
from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.data import SyntheticDataset
from repro.launch import shapes as shp
from repro.launch.env import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import jit_train_step, param_shardings
from repro.models import init_lm
from repro.optim import OptConfig, init_opt_state


def scaled_config(cfg, scale: float):
    """Shrink a config by ~scale in parameter count for examples/CI
    (keeps family structure; used for the ~100M-class training example)."""
    if scale >= 1.0:
        return cfg
    import math
    f = max(0.05, math.sqrt(scale))
    d = max(128, int(cfg.d_model * f) // 128 * 128)
    heads = max(2, int(cfg.num_heads * f))
    ratio = max(1, cfg.num_heads // cfg.num_kv_heads)
    groups = tuple((p, max(1, int(r * f))) for p, r in cfg.groups)
    enc = tuple((p, max(1, int(r * f))) for p, r in cfg.encoder_groups)
    return dataclasses.replace(
        cfg, d_model=d, num_heads=heads, num_kv_heads=max(1, heads // ratio),
        d_ff=max(256, int(cfg.d_ff * f) // 128 * 128),
        vocab_size=min(cfg.vocab_size, 32768),
        groups=groups, encoder_groups=enc, head_dim=None,
        num_experts=min(cfg.num_experts, 8) if cfg.num_experts else 0,
    )


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None, help="override seq len")
    ap.add_argument("--batch", type=int, default=None, help="override global batch")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="model scale factor (e.g. 0.02 for a ~100M llama)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8_e4m3", "fp8_e5m2"])
    ap.add_argument("--rotate", default="none", choices=["none", "hadamard"])
    ap.add_argument("--kernel", default="xla", choices=["xla", "pallas"],
                    help="online-rotation backend (pallas = hadacore)")
    ap.add_argument("--opt-state", default="f32", choices=["f32", "int8"])
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8_ef"])
    ap.add_argument("--mp", type=int, default=1, help="model-parallel size")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--straggler-z", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    quant = QuantConfig(mode=args.quant, rotate=args.rotate,
                        backend=args.kernel, kv_quant=args.quant != "none")
    cfg = scaled_config(get_config(args.arch), args.scale).with_quant(quant)
    shape = shp.SHAPES[args.shape]
    if args.seq or args.batch:
        shape = dataclasses.replace(shape, seq=args.seq or shape.seq,
                                    batch=args.batch or shape.batch)
    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(1, args.steps // 20),
                        state_dtype=args.opt_state,
                        grad_compression=args.grad_compression)

    mesh = make_local_mesh(args.mp)
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} | "
          f"arch {cfg.name} scale {args.scale} | {shape}")

    step_fn, (ps, os_, bs) = jit_train_step(cfg, opt_cfg, shape, mesh,
                                            microbatches=args.microbatch)

    start_step = 0
    if args.ckpt_dir and (lk := latest_step(args.ckpt_dir)) is not None:
        print(f"restoring checkpoint step {lk}")
        import functools
        pshapes = jax.eval_shape(functools.partial(init_lm, cfg=cfg),
                                 jax.random.PRNGKey(args.seed))
        oshapes = jax.eval_shape(lambda: init_opt_state(pshapes, opt_cfg))
        params = restore_checkpoint(args.ckpt_dir, lk, pshapes, ps)
        opt_state = restore_checkpoint(args.ckpt_dir + "/opt", lk, oshapes, os_)
        start_step = lk
    else:
        with mesh:
            params = jax.jit(lambda k: init_lm(k, cfg), out_shardings=ps)(
                jax.random.PRNGKey(args.seed))
            opt_state = jax.jit(lambda: init_opt_state(params, opt_cfg),
                                out_shardings=os_)()
    n_params = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M")

    ds = SyntheticDataset(cfg, shape, seed=args.seed)
    stop = {"now": False}

    def handle(sig, frame):
        print(f"signal {sig}: checkpointing and exiting")
        stop["now"] = True

    signal.signal(signal.SIGTERM, handle)
    signal.signal(signal.SIGINT, handle)

    times = []
    t_train0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jax.device_put(v, bs[k]) for k, v in ds.batch(step).items()}
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        metrics = jax.tree.map(float, jax.device_get(metrics))
        dt = time.time() - t0
        times.append(dt)
        if len(times) > 5:
            mu, sd = np.mean(times[1:]), np.std(times[1:]) + 1e-9
            if dt > mu + args.straggler_z * sd:
                print(f"[straggler] step {step}: {dt:.2f}s vs mean {mu:.2f}s "
                      f"(z={ (dt-mu)/sd:.1f}) -- flagging host set for quarantine")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"gnorm {metrics['gnorm']:.3f} lr {metrics['lr']:.2e} {dt:.2f}s")
        if args.ckpt_dir and ((step + 1) % args.ckpt_every == 0 or stop["now"]
                              or step == args.steps - 1):
            save_checkpoint(args.ckpt_dir, step + 1, params)
            save_checkpoint(args.ckpt_dir + "/opt", step + 1, opt_state)
        if stop["now"]:
            wait_for_writes()
            sys.exit(0)
    wait_for_writes()
    total = time.time() - t_train0
    print(f"done: {args.steps - start_step} steps in {total:.1f}s "
          f"({np.mean(times[1:]) if len(times) > 1 else times[0]:.2f}s/step)")


if __name__ == "__main__":
    main()
