"""Bring-up check on a TPU: the serving path once, at full width.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --chips 4     # the four-chip phase, alone

On one chip it runs, in one process:

  (a) a device check -- anything but a TPU exits non-zero here;
  (b) the Pallas kernels of the main path at Phi-4-mini's widths,
      compiled by Mosaic and run on the chip, each compared with the XLA
      path and checked for a ``tpu_custom_call`` in its compiled program;
  (c) Phi-4-mini-3.8B at its published widths (random weights from
      ``--seed``) served through ``ServeEngine`` over a seeded stream of
      16 requests, fp8 rotation-quantized with prequantized weights and
      the Pallas kernels, failing on any non-``ok`` request, any
      degradation, a second decode executable, a weight quantization
      during serving, or any kernel-fallback counter above zero;
  (d) one JSON line, ``{"ok": true, "device": {...}}``, last.

``--chips 4`` instead serves Phi-4-mini tensor-parallel on a (1, 4) mesh:
it compares the sharded int8 ``quant_dot`` bitwise with one device and
the first decode step with the same engine on a one-device mesh, then
serves (c)'s stream on the four chips under (c)'s checks.

Every failure exits non-zero before the JSON line. The script needs the
checkout's ``src/`` beside it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "phi4-mini-3.8b"
MODE = "fp8_e4m3"                   # the paper's deployment
D_FF, D_MODEL = 8192, 3072          # Phi-4-mini's w_down: (d_ff, d_model)
HEAD_DIM = 128                      # the QK rotation site
# counters of a kernel quietly replaced by a slower path; all must stay 0
FALLBACK_KEYS = (("quant_dot", "vmem_unfused"),
                 ("quant_dot", "stream_fallback"))
FALLBACK_KINDS = ("backend_fallback", "sharded_quant_dot")
# --chips 4: largest per-slot relative L2 error of the tensor-parallel
# first-step logits against one device. Healthy, it read 0.083 on four
# v5e chips. A fault planted in the (1, 4) engine's rotated
# down-projection -- one of four column shards scaled by its neighbour's
# per-channel weight scales -- read 0.134 there. On four CPU devices at
# the 4-layer width of scale 1/64: 0.028 healthy, 0.113 with that
# fault, 0.916 with one shard returning its neighbour's columns.
TP_REL_TOL = 0.1
# ... and the rank within the one-device logits that each tensor-parallel
# greedy token must reach
TP_TOP_K = 10


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the time JAX spends in backend compiles (a persistent-cache
    read is timed as the compile it replaces) and counts the persistent
    cache's hits and the entries it writes, through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.programs, self.hits, self.writes = 0.0, 0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":   # a write
            self.writes += 1


# ------------------------------------------------------------ (a) device
def device_check(chips: int):
    import jax

    devices = jax.devices()
    print(f"devices: {devices}")
    for d in devices:
        print(f"  id={d.id} platform={d.platform} kind={d.device_kind}")
    check(devices[0].platform == "tpu",
          f"no TPU: JAX runs on {devices[0].platform!r}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, found {len(devices)}")
    return devices


# ------------------------------------------------------------ (b) kernels
def _compiled(fn, *args):
    """Compile ``fn`` for the chip, run it, and report whether the
    program holds a Mosaic kernel (a ``tpu_custom_call``)."""
    import jax

    c = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(c(*args))
    return out, "tpu_custom_call" in c.as_text()


def _max_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def kernel_phase(seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.api import QuantEpilogue, hadamard, plan_for, quant_dot
    from repro.core.wquant import quantize_weight

    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    # transform, bf16: the tolerance of test_hadacore_dtypes
    for n in (HEAD_DIM, D_FF):
        x = jnp.asarray(rng.standard_normal((4096, n)), bf16)
        got, kernel = _compiled(lambda a: hadamard(a, backend="pallas"), x)
        want, _ = _compiled(lambda a: hadamard(a, backend="xla"), x)
        err, scale = _max_err(got, want)
        print(f"kernel hadamard n={n} bf16 (4096 rows): max_abs_err={err} "
              f"tpu_custom_call={kernel}")
        check(kernel, f"hadamard n={n} compiled without a Mosaic kernel")
        check(err <= 2e-2 * scale + 2e-1,
              f"hadamard n={n} differs from the XLA path by {err}")

    # the QK site: rotate + fake-quantize in one kernel; the tolerance of
    # test_plan_api's fused-dequant check for this mode
    rel = {"int8": 1 / 50, "fp8_e4m3": 1 / 20}[MODE]
    x = jnp.asarray(rng.standard_normal((4096, HEAD_DIM)), bf16)
    for backend in ("pallas", "xla"):
        plan = plan_for(HEAD_DIM, dtype=bf16, backend=backend,
                        epilogue=QuantEpilogue(MODE, dequant=True))
        out = _compiled(lambda a, plan=plan: hadamard(a, plan), x)
        if backend == "pallas":
            got, kernel = out
        else:
            want = out[0]
    err, scale = _max_err(got, want)
    print(f"kernel rotate+quantize n={HEAD_DIM} {MODE} (QK site): "
          f"max_abs_err={err} tpu_custom_call={kernel}")
    check(kernel, "the fused QK kernel compiled without a Mosaic kernel")
    check(err <= rel * scale,
          f"the fused QK kernel differs from the XLA path by {err}")

    # rotate -> quantize -> GEMM against Phi-4-mini's w_down, decode and
    # prefill rows: the bf16 tolerance of test_quant_dot. The weight is an
    # argument, not a closure: a closed-over weight is baked into each
    # executable, which then fills the compile cache.
    w = jnp.asarray(rng.standard_normal((D_FF, D_MODEL)) * 0.05, bf16)
    for qmode in ("int8", "fp8_e4m3"):
        qt = quantize_weight(w, qmode)
        for m in (8, 2048):
            x = jnp.asarray(rng.standard_normal((m, D_FF)), bf16)
            want, _ = _compiled(
                lambda a, q, qmode=qmode: quant_dot(
                    a, q, mode=qmode, backend="xla"), x, qt)
            for schedule in ("rotate_once", "streamed"):
                got, kernel = _compiled(
                    lambda a, q, qmode=qmode, schedule=schedule:
                    quant_dot(a, q, mode=qmode, backend="pallas",
                              schedule=schedule), x, qt)
                err, scale = _max_err(got, want)
                print(f"kernel quant_dot {qmode} {schedule} x=({m}, {D_FF}) "
                      f"w=({D_FF}, {D_MODEL}): max_abs_err={err} "
                      f"rel={err / scale} tpu_custom_call={kernel}")
                check(kernel, f"quant_dot {qmode} {schedule} m={m} "
                              "compiled without a Mosaic kernel")
                check(err <= 5e-2 * scale,
                      f"quant_dot {qmode} {schedule} m={m} differs from "
                      f"the XLA path by {err / scale} of its range")


# ------------------------------------------------------------ (c) serving
def serve_args(seed: int, mp: int = 1):
    from repro.launch import serve_loop

    return serve_loop.parse_args([
        "--arch", ARCH, "--scale", "1.0", "--slots", "8",
        "--max-len", "2048", "--prefill-len", "512",
        "--requests", "16", "--prompt-min", "64",
        "--prompt-max", "512", "--gen-min", "32", "--gen-max", "128",
        "--quant", MODE, "--rotate", "hadamard", "--kernel", "pallas",
        "--prequant", "--mp", str(mp), "--seed", str(seed)])


def fallback_counts() -> dict:
    from repro.kernels.registry import TRACE_COUNTS

    return {k: v for k, v in TRACE_COUNTS.items()
            if k in FALLBACK_KEYS or k[0] in FALLBACK_KINDS}


def serving_phase(seed: int, mp: int = 1) -> None:
    """Serve the seeded stream on a (1, mp) mesh of the first mp devices
    and check every request, the engine's health and the counters."""
    import jax

    from repro.kernels.registry import TRACE_COUNTS
    from repro.launch import serve_loop

    args = serve_args(seed, mp)
    kernel_traces = TRACE_COUNTS[("pallas", "quant_dot")]
    t0 = time.perf_counter()
    engine, cfg = serve_loop.build_engine(args)
    print(f"engine: {ARCH} at published widths (d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} layers={sum(r for _, r in cfg.groups)} "
          f"vocab={cfg.vocab_size}) on a {engine.mesh.devices.shape} mesh, "
          f"{MODE} rotation-quantized, prequantized weights, built in "
          f"{time.perf_counter() - t0:.1f}s")
    compile_s = engine.warmup()
    print(f"compile: prefill/insert/decode compiled in {compile_s:.2f}s")
    engine.run(serve_loop.make_stream(args, cfg))
    s = serve_loop.report(engine)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    served = TRACE_COUNTS[("pallas", "quant_dot")] - kernel_traces
    fallbacks = fallback_counts()
    print(f"pallas quant_dot traces while serving: {served}; "
          f"fallback counters: {fallbacks or 'none'}")

    statuses = [c.status for c in engine.completions]
    check(len(statuses) == args.requests
          and all(st == "ok" for st in statuses),
          f"requests not all ok: {statuses}")
    degrades = s["health"]["degrades"]
    check(degrades == 0 and s["rung"] == 0,
          f"engine degraded: degrades={degrades} rung={s['rung']}")
    check(s["decode_executables"] == 1,
          f"decode_executables={s['decode_executables']}")
    check(s["quantize_weight_calls"] == 0,
          f"quantize_weight_calls={s['quantize_weight_calls']}")
    check(not any(fallbacks.values()), f"kernel fallbacks: {fallbacks}")
    check(served > 0, "the Pallas quant_dot never traced while serving")


# ---------------------------------------------------------- --chips 4
def first_decode_step(engine, requests, tokens=None):
    """Admit ``requests`` (one per slot) and run one decode step on the
    prefill tokens, or on ``tokens`` where given so that two engines
    decode the same input. Returns (prefill tokens, logits, tokens)."""
    import numpy as np

    engine.warmup()
    for r in requests:
        engine.submit(r)
    while (adm := engine.sched.next_admission(0.0)) is not None:
        engine._admit(*adm)
    first = engine.tokens_h[:, 0].copy()
    if tokens is not None:
        engine.tokens_h[:, 0] = tokens
    new_tok, logits, engine.caches = engine._dispatch_decode()
    return (first, np.asarray(logits[:, -1], np.float32),
            np.asarray(new_tok)[:, 0])


def four_chip_phase(seed: int) -> None:
    """Tensor-parallel serving on a (1, 4) mesh: the sharded int8
    quant_dot bitwise against one device; the first decode step of the
    same requests against the same engine on a one-device mesh of
    ``devices()[0]``; then phase (c)'s stream and checks on four chips."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import api
    from repro.core.wquant import quantize_weight
    from repro.distributed import sharding as shd
    from repro.launch import serve_loop
    from repro.launch.mesh import make_local_mesh
    from repro.serving.scheduler import Request

    devices = jax.devices()[:4]
    mesh4 = make_local_mesh(4, devices)
    rng = np.random.default_rng(seed)

    # the sharded fused quant_dot: w_down's out-channels over 'model'
    qt = quantize_weight(jnp.asarray(
        rng.standard_normal((D_FF, D_MODEL)) * 0.05, jnp.bfloat16), "int8")
    for m in (8, 512):
        x = jnp.asarray(rng.standard_normal((m, D_FF)), jnp.bfloat16)
        ref = api.quant_dot(x, qt, mode="int8", backend="pallas")
        xs, qs = jax.device_put((x, qt), NamedSharding(mesh4, P()))

        def sharded(a, q):
            with shd.sharding_rules(mesh4):
                return api.quant_dot(a, q, mode="int8", backend="pallas",
                                     weight_axes=(None, "dff"))

        out = jax.jit(sharded)(xs, qs)
        disp = dict(api._LAST_SHARDED_DISPATCH)
        same = bool((np.asarray(out) == np.asarray(ref)).all())
        print(f"sharded quant_dot int8 x=({m}, {D_FF}) w=({D_FF}, "
              f"{D_MODEL}) over {disp.get('mesh_axes')}: "
              f"fused={disp.get('fused')} bitwise_equal={same}")
        check(disp.get("fused") and disp.get("mesh_axes") == ("model",),
              f"the sharded quant_dot did not run the fused kernel: {disp}")
        check(same, "sharded int8 quant_dot differs from one device")

    # one decode step of the same requests on both meshes
    args = serve_args(seed, mp=4)
    reqs = [Request(rid=i, tokens=rng.integers(
        0, 32000, (int(rng.integers(args.prompt_min, args.prompt_max + 1)),),
        dtype=np.int32), max_new_tokens=8, arrival_time=0.0)
        for i in range(args.slots)]
    one, cfg = serve_loop.build_engine(
        args, mesh=make_local_mesh(1, devices[:1]))
    tok1, logits1, next1 = first_decode_step(one, reqs)
    del one
    gc.collect()
    four, _ = serve_loop.build_engine(args, mesh=mesh4)
    tok4, logits4, next4 = first_decode_step(four, reqs, tokens=tok1)
    del four
    gc.collect()
    fallbacks = fallback_counts()

    logits1 = logits1[:, :cfg.vocab_size]
    logits4 = logits4[:, :cfg.vocab_size]
    err = float(np.abs(logits4 - logits1).max())
    span = float(np.abs(logits1).max())
    # per-slot relative L2 error. The (1, 4) mesh sums its row-parallel
    # products in another order, and the rounding of those sums grows
    # through 32 layers and the fp8 rotation sites (0.083 on the chip).
    # A sharding fault moves the logits further: see TP_REL_TOL.
    rel = float((np.linalg.norm(logits4 - logits1, axis=-1)
                 / np.linalg.norm(logits1, axis=-1)).max())
    # The random model's logits are nearly flat at the top, so rounding
    # may swap its greedy token for a close runner-up; a fault picks a
    # token far down the one-device ranking.
    top = np.argsort(logits1, axis=-1)[:, -TP_TOP_K:]
    in_top = (top == next4[:, None]).any(axis=-1)
    print(f"tensor-parallel (1, 4) vs one device, first decode step: "
          f"max_rel_l2_logit_err={rel} max_abs_logit_err={err} (logit "
          f"range {span}); greedy tokens agree on "
          f"{int((next4 == next1).sum())}/{len(next1)} slots, within the "
          f"one-device top {TP_TOP_K} on {int(in_top.sum())}/{len(in_top)}; "
          f"prefill tokens agree on {int((tok4 == tok1).sum())}/{len(tok1)}"
          f"; fallback counters: {fallbacks or 'none'}")
    check(rel <= TP_REL_TOL,
          f"first-step logits differ by {rel} relative L2 "
          f"(tolerance {TP_REL_TOL})")
    check(bool(in_top.all()), "a greedy token of the (1, 4) engine is not "
                              f"among the one-device top {TP_TOP_K}")
    check(not any(fallbacks.values()), f"kernel fallbacks: {fallbacks}")

    # the same stream as phase (c), served tensor-parallel; with the
    # in-memory caches cleared, its programs trace anew, so that phase
    # (c)'s kernel-trace check reads this engine
    jax.clear_caches()
    serving_phase(seed, mp=4)


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: no repro package under {SRC}; run the "
              "script from a checkout", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch.env import enable_compile_cache, harden_host_env

    harden_host_env(reexec=False)
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    try:
        devices = device_check(args.chips)
        if args.chips == 4:
            four_chip_phase(args.seed)
        else:
            kernel_phase(args.seed)
            serving_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"compile: {clock.programs} programs, {clock.seconds:.2f}s in "
          f"backend compiles; persistent cache {cache_dir}: "
          f"{clock.hits} hits, {clock.writes} entries written")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
