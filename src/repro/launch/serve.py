"""Batched serving launcher: prefill a prompt batch, then decode with the
(optionally FP8-quantized, Hadamard-rotated) KV cache -- the paper's
deployment scenario.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \
        --scale 0.02 --batch 8 --prompt-len 128 --gen 32 \
        --quant fp8_e4m3 --rotate hadamard
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.launch import shapes as shp
from repro.launch.env import enable_compile_cache, harden_host_env
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import (
    jit_prefill_step,
    jit_serve_step,
    make_param_init,
    param_shardings,
)
from repro.launch.train import scaled_config
from repro.models.lm import pad_kv_caches


def main(argv=None):
    harden_host_env()                 # flags only; re-exec is __main__'s
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--quant", default="none",
                    choices=["none", "int8", "fp8_e4m3", "fp8_e5m2"])
    ap.add_argument("--rotate", default="none", choices=["none", "hadamard"])
    ap.add_argument("--kernel", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--prequant", dest="prequant", action="store_true",
                    default=None,
                    help="pre-quantize weights ONCE at load into QTensors "
                         "(storage int8; rotation-consumer weights in the "
                         "serving quant mode, consumed by quant_dot with "
                         "zero per-forward weight quantization). Default: "
                         "on whenever --quant is not 'none'.")
    ap.add_argument("--no-prequant", dest="prequant", action="store_false")
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    quant = QuantConfig(mode=args.quant, rotate=args.rotate,
                        backend=args.kernel, kv_quant=args.quant != "none")
    cfg = scaled_config(get_config(args.arch), args.scale).with_quant(quant)
    prequant = args.quant != "none" if args.prequant is None else args.prequant
    if prequant:
        cfg = dataclasses.replace(cfg, weight_quant="int8")
    mesh = make_local_mesh(args.mp)
    max_len = args.prompt_len + args.gen

    with mesh:
        # param_shardings / make_param_init are QTensor-aware: with
        # --prequant the weights come out of this one jit already
        # quantized and never re-quantize per forward
        ps = param_shardings(cfg, mesh)
        params = jax.jit(make_param_init(cfg), out_shardings=ps)(
            jax.random.PRNGKey(args.seed))
    if prequant:
        print("weights pre-quantized once at load (QTensor tree; "
              f"consumer mode={args.quant})")

    shape = shp.ShapeSpec("serve", "prefill", args.prompt_len, args.batch)
    prefill, (ps_, bs) = jit_prefill_step(cfg, shape, mesh)
    serve, _ = jit_serve_step(cfg, args.batch, max_len, mesh, donate=True)

    batch = shp.make_batch(cfg, shape, seed=args.seed)
    t0 = time.time()
    logits, caches = prefill(params, batch)
    caches = pad_kv_caches(cfg, caches, max_len)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    t_prefill = time.time() - t0
    print(f"prefill: B={args.batch} S={args.prompt_len} in {t_prefill:.2f}s")

    out_tokens = [np.asarray(tok)]
    pos = args.prompt_len + (cfg.vlm_patches if cfg.family == "vlm" else 0)
    # the first serve() call pays the jit compile -- warm it up OUTSIDE
    # the timed loop (its token is still step 0's real output) so the
    # reported tok/s is steady-state decode, not compile-dominated
    t0 = time.time()
    steps = 0
    if args.gen > 1:
        tok, _, caches = serve(params, caches, tok,
                               jnp.asarray(pos, jnp.int32))
        out_tokens.append(np.asarray(tok))
        t_warm = time.time() - t0
        t0 = time.time()
        for i in range(1, args.gen - 1):
            tok, _, caches = serve(params, caches, tok,
                                   jnp.asarray(pos + i, jnp.int32))
            out_tokens.append(np.asarray(tok))
        steps = args.gen - 2
    dt = time.time() - t0
    toks = np.concatenate(out_tokens, axis=1)
    if steps > 0:
        print(f"decode: first step {t_warm:.2f}s (incl. jit compile); "
              f"{steps} steady-state steps in {dt:.2f}s "
              f"({steps * args.batch / max(dt, 1e-9):.1f} tok/s)")
    else:
        print(f"decode: {args.gen - 1} steps in {dt:.2f}s (0.0 tok/s "
              "steady-state; too few steps to separate compile)")
    print("sample token ids:", toks[0, :16].tolist())


if __name__ == "__main__":
    harden_host_env(reexec=True)
    main()
