"""Site tracing for the kernel contract linter.

A ``Site`` is one traced artifact the rules run over: a fused-kernel
dispatch (its jaxpr + the ``BlockDecision`` the planner charged), a
model forward (MLP down-projection through the bound spec), or a
serving executable (decode step / prefill-insert: jaxpr + compiled HLO
+ the cache leaf shapes the donation contract covers).

Builders trace through the SAME entry points the model/serving layers
use (``pallas_quant_dot``, ``apply_mlp``, ``ServeEngine``) so the lint
asserts the code paths production takes, not a lookalike. Every trace
records the ``quantize_weight`` call delta and the deprecated-shim
``TRACE_COUNTS`` deltas, which the fusion and deprecated-shim rules
consume.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Site", "kernel_sites", "w_down_sites", "model_sites",
           "serving_sites", "default_sites", "traced"]

_SHIM_KEYS = (
    ("deprecated", "kernels.ops.hadamard"),
    ("deprecated", "kernels.fused_quant.fused_hadamard_quantize"),
)


@dataclasses.dataclass
class Site:
    """One traced artifact plus the static facts the rules check it
    against. Fields are optional by design: each rule's ``applies()``
    keys off what the site carries (a kernel site has a plan+decision,
    a serving site HLO + cache leaves, ...)."""

    name: str
    kind: str                               # "kernel" | "model" | "serving"
    jaxpr: Any = None                       # ClosedJaxpr of the trace
    schedule: Optional[str] = None          # resolved kernel schedule
    plan: Any = None                        # HadamardPlan
    decision: Any = None                    # BlockDecision actually charged
    io_dtype: Any = None
    hlo_text: Optional[str] = None          # compiled HLO (serving sites)
    cache_leaves: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    donated: bool = False                   # cache donation is contractual
    qw_calls: int = 0                       # quantize_weight delta in-trace
    shim_calls: Dict[str, int] = dataclasses.field(default_factory=dict)
    expect_fused: bool = True


def traced(fn, *args):
    """``jax.make_jaxpr`` of ``fn(*args)``, returning the jaxpr plus the
    in-trace ``quantize_weight`` call delta and deprecated-shim call
    deltas (the counters the fusion / deprecated-shim rules read)."""
    import jax

    from repro.core import wquant
    from repro.kernels.registry import TRACE_COUNTS

    qw0 = wquant.QUANTIZE_WEIGHT_CALLS
    shim0 = {k: TRACE_COUNTS[k] for k in _SHIM_KEYS}
    jaxpr = jax.make_jaxpr(fn)(*args)
    shim = {"/".join(k): TRACE_COUNTS[k] - shim0[k] for k in _SHIM_KEYS}
    return jaxpr, wquant.QUANTIZE_WEIGHT_CALLS - qw0, shim


@contextlib.contextmanager
def _stream_interpret_forced():
    """Run the real streamed kernel bodies on the interpreter's
    synchronous DMA simulation (the force flag CI's streamed leg uses),
    restoring the env afterwards."""
    from repro.kernels.quant_dot import STREAM_INTERPRET_ENV

    prev = os.environ.get(STREAM_INTERPRET_ENV)
    os.environ[STREAM_INTERPRET_ENV] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(STREAM_INTERPRET_ENV, None)
        else:
            os.environ[STREAM_INTERPRET_ENV] = prev


def _scaled(config_name: str):
    from repro.configs import get_config
    from repro.launch.train import scaled_config

    return scaled_config(get_config(config_name), 0.004)


def kernel_sites(config_name: str, schedule: str = "rotate_once",
                 *, block_n: int = 128, abft: bool = False) -> List[Site]:
    """The fused quant_dot dispatches for ``config_name``: the 2-D
    dense kernel and the 3-D stacked-expert kernel, traced at the
    config's io dtype on a lint-sized problem (n = the 0.004-scaled
    d_model, d = 5 out-channel tiles so the streamed ring actually
    cycles). ``abft=True`` traces the checksum-VERIFIED twins instead
    (stored column checksum in, (out, residual) out) -- the lint proof
    that the verification column does not break the one-pallas_call
    fusion, the rotate-once dot counts, or the streamed DMA ring."""
    import jax.numpy as jnp

    from repro.core.api import QuantEpilogue, plan_for
    from repro.core.wquant import weight_checksum
    from repro.kernels.quant_dot import (pallas_quant_dot,
                                         pallas_quant_dot_experts,
                                         quant_dot_blocks)

    cfg = _scaled(config_name)
    n, d, m = cfg.d_model, 5 * block_n, 8
    io = jnp.dtype(cfg.dtype)
    plan = plan_for(n, dtype=io, backend="pallas",
                    epilogue=QuantEpilogue("int8"))
    tag = f"{schedule}/abft" if abft else schedule
    ctx = (_stream_interpret_forced() if schedule == "streamed"
           else contextlib.nullcontext())
    sites = []
    with ctx:
        x = jnp.zeros((m, n), io)
        wq = jnp.zeros((n, d), jnp.int8)
        sw = jnp.ones((1, d), jnp.float32)
        decision = quant_dot_blocks(n, d, m, io, plan.compute_dtype,
                                    "int8", block_m=plan.block_m,
                                    block_n=block_n, schedule=schedule,
                                    abft=abft)
        if abft:
            cw = weight_checksum(wq, sw)
            jaxpr, qw, shim = traced(
                lambda a, q, s, c: pallas_quant_dot(a, q, s, plan, True,
                                                    schedule, block_n,
                                                    check=c),
                x, wq, sw, cw)
        else:
            jaxpr, qw, shim = traced(
                lambda a, q, s: pallas_quant_dot(a, q, s, plan, True,
                                                 schedule, block_n),
                x, wq, sw)
        sites.append(Site(
            name=f"quant_dot[{config_name}/{tag}]", kind="kernel",
            jaxpr=jaxpr, schedule=schedule, plan=plan, decision=decision,
            io_dtype=io, qw_calls=qw, shim_calls=shim))

        xe = jnp.zeros((1, 2, m, n), io)
        wqe = jnp.zeros((2, n, d), jnp.int8)
        swe = jnp.ones((2, 1, d), jnp.float32)
        if abft:
            cwe = weight_checksum(wqe, swe)
            jaxpr, qw, shim = traced(
                lambda a, q, s, c: pallas_quant_dot_experts(
                    a, q, s, plan, True, schedule, block_n, check=c),
                xe, wqe, swe, cwe)
        else:
            jaxpr, qw, shim = traced(
                lambda a, q, s: pallas_quant_dot_experts(a, q, s, plan,
                                                         True, schedule,
                                                         block_n),
                xe, wqe, swe)
        sites.append(Site(
            name=f"quant_dot_experts[{config_name}/{tag}]",
            kind="kernel", jaxpr=jaxpr, schedule=schedule, plan=plan,
            decision=decision, io_dtype=io, qw_calls=qw, shim_calls=shim))
    return sites


def w_down_sites(schedule: str = "rotate_once",
                 config_name: str = "phi4_mini_3_8b") -> List[Site]:
    """The fp8 w_down dispatch of ``config_name`` at its published
    widths (n = d_ff, d = d_model; Phi-4-mini: 8192 x 3072, bf16 io),
    traced on abstract shapes at a decode batch (m = 32) and a prefill
    bucket (m = 1024): the sizes at which the tile decision has to give
    the row block room, charged and checked as it runs there."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import pallas_quant_dot, quant_dot_blocks

    cfg = get_config(config_name)
    n, d, mode = cfg.d_ff, cfg.d_model, "fp8_e4m3"
    io = jnp.dtype(cfg.dtype)
    plan = plan_for(n, dtype=io, backend="pallas",
                    epilogue=QuantEpilogue(mode))
    wq = jax.ShapeDtypeStruct((n, d), jnp.float8_e4m3fn)
    sw = jax.ShapeDtypeStruct((1, d), jnp.float32)
    ctx = (_stream_interpret_forced() if schedule == "streamed"
           else contextlib.nullcontext())
    sites = []
    with ctx:
        for m in (32, 1024):
            decision = quant_dot_blocks(n, d, m, io, plan.compute_dtype,
                                        mode, schedule=schedule)
            jaxpr, qw, shim = traced(
                lambda a, q, s: pallas_quant_dot(a, q, s, plan, True,
                                                 schedule),
                jax.ShapeDtypeStruct((m, n), io), wq, sw)
            sites.append(Site(
                name=f"w_down[{config_name}/{schedule}/m={m}]",
                kind="kernel", jaxpr=jaxpr, schedule=schedule, plan=plan,
                decision=decision, io_dtype=io, qw_calls=qw,
                shim_calls=shim))
    return sites


def model_sites(config_name: str) -> List[Site]:
    """The bound-spec model forward: the scaled config's MLP with a
    fusable pow-2 down-projection, int8 pallas quantization -- the
    PR 4 spec path every model site routes through."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.quant import QuantConfig
    from repro.models.mlp import apply_mlp, init_mlp

    cfg = get_config(config_name).scaled_down(
        d_model=256, d_ff=512).with_quant(
        QuantConfig(mode="int8", rotate="hadamard", backend="pallas"))
    p = init_mlp(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 4, cfg.d_model), jnp.dtype(cfg.dtype))
    jaxpr, qw, shim = traced(lambda a: apply_mlp(cfg, p, a), x)
    return [Site(name=f"mlp_down_proj[{config_name}]", kind="model",
                 jaxpr=jaxpr, io_dtype=jnp.dtype(cfg.dtype),
                 qw_calls=qw, shim_calls=shim)]


def _cache_leaves(caches) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
    import jax

    return tuple((tuple(leaf.shape), str(leaf.dtype))
                 for leaf in jax.tree_util.tree_leaves(caches))


def serving_sites(config_name: str, *, backend: str = "xla",
                  engine=None) -> List[Site]:
    """The serving executables: the donated per-slot decode step and
    the donated prefill-insert, traced + compiled from a real (scaled)
    ``ServeEngine`` so the donation contract is checked on the exact
    executables the engine dispatches. Pass ``engine=`` to lint an
    already-built (possibly degraded/re-warmed) engine instead."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    if engine is None:
        from repro.configs import get_config
        from repro.core.quant import QuantConfig
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import make_param_init, param_shardings
        from repro.launch.train import scaled_config
        from repro.serving import ServeEngine

        quant = QuantConfig(mode="fp8_e4m3", rotate="hadamard",
                            backend=backend, kv_quant=True)
        cfg = scaled_config(get_config(config_name), 0.004).with_quant(quant)
        cfg = _dc.replace(cfg, weight_quant="int8")
        mesh = make_local_mesh(1)
        with mesh:
            ps = param_shardings(cfg, mesh)
            params = jax.jit(make_param_init(cfg), out_shardings=ps)(
                jax.random.PRNGKey(0))
        engine = ServeEngine(cfg, params, mesh, num_slots=2, max_len=32,
                             prefill_len=8)

    leaves = _cache_leaves(engine.caches)
    tok = jnp.asarray(engine.tokens_h)
    pos = jnp.asarray(engine.positions_h)
    decode_args = (engine.params, engine.caches, tok, pos)
    jaxpr, qw, shim = traced(engine._decode, *decode_args)
    hlo = engine._decode.lower(*decode_args).compile().as_text()
    decode = Site(
        name=f"serve_decode[{config_name}/rung{engine._rung}]",
        kind="serving", jaxpr=jaxpr, io_dtype=jnp.dtype(engine.cfg.dtype),
        hlo_text=hlo, cache_leaves=leaves, donated=True,
        qw_calls=qw, shim_calls=shim)

    batch = {"tokens": jnp.zeros((1, engine.prefill_len), jnp.int32)}
    out = engine._prefill(engine.params, batch, jnp.asarray(1, jnp.int32))
    kv = out[-1]
    insert_args = (engine.caches, kv, jnp.asarray(0, jnp.int32))
    ijaxpr, iqw, ishim = traced(engine._insert, *insert_args)
    ihlo = engine._insert.lower(*insert_args).compile().as_text()
    insert = Site(
        name=f"serve_insert[{config_name}]", kind="serving", jaxpr=ijaxpr,
        io_dtype=jnp.dtype(engine.cfg.dtype), hlo_text=ihlo,
        cache_leaves=leaves, donated=True, qw_calls=iqw, shim_calls=ishim,
        expect_fused=False)  # insert is a cache scatter: no kernel, no dot
    return [decode, insert]


def default_sites(config_name: str, schedule: str = "rotate_once",
                  *, serving: bool = True, abft: bool = False) -> List[Site]:
    """Every lintable site for one (config, schedule) pair. ``abft=True``
    additionally lints the checksum-verified kernel twins."""
    sites = kernel_sites(config_name, schedule)
    if abft:
        sites += kernel_sites(config_name, schedule, abft=True)
    sites += w_down_sites(schedule)
    sites += model_sites(config_name)
    if serving:
        sites += serving_sites(config_name)
    return sites
