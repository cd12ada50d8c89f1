"""Unified plan-based Hadamard API: one entry point for every transform.

This is the seam the whole repo routes rotations through (DESIGN.md
section 5). Instead of four divergent entry points with string-typed
knobs, callers build (or let us cache) a :class:`HadamardPlan` --
everything shape-dependent is precomputed exactly once per
``(n, dtype, compute_dtype, backend, epilogue, scale, block_m)`` key:

  * the pass matrices of ``H_n = H_a (x) H_b`` (b = min(n, 128), one
    lane pass and one sublane pass; ``hadamard.base_matrices_np``) with
    the scale folded into the first;
  * the resolved backend (registry lookup: explicit > env override >
    auto-by-size/platform);
  * the VMEM row-tile ``block_m``.

and ``hadamard(x, plan)`` dispatches. Composable epilogues make the fused
rotate+quantize kernel the default hot path:

  * ``epilogue=None``                     -> rotated tensor
  * ``QuantEpilogue("int8"|"fp8_e4m3"|"fp8_e5m2", per_token=True)``
                                          -> ``(q, scales)`` from a single
                                             VMEM-resident kernel
  * ``QuantEpilogue(..., dequant=True)``  -> fake-quantized rotated tensor
                                             (training path), same single
                                             kernel

Non-power-of-2 sizes are handled by the grouped transform I_g (x) H_p
with p the largest power-of-2 divisor (DESIGN.md section 3): the plan
carries both ``n`` (full axis) and ``p`` (per-group transform size), and
epilogue scales stay per-FULL-token (computed outside the kernel in that
case, so grouped semantics match the historical two-step path).

Autodiff: the transform is its own adjoint (H symmetric, scale scalar),
so the pullback is one more transform. Epilogue paths carry the
straight-through estimator: quantization is treated as identity in the
backward pass, so ``d(q)/dx ~= H/s`` and ``d(dequant)/dx ~= H``. This is
a DELIBERATE training-numerics upgrade over differentiating the unfused
``quantize(hadamard(x))`` directly, whose ``round()`` has zero gradient
almost everywhere (only the absmax scale branch leaks signal) -- the STE
is the standard QAT estimator and is what the fused path exists to serve.
Forward numerics are unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.dtypes import float0

from repro.core.hadamard import (
    base_matrices_np,
    largest_pow2_divisor,
    pack_pass_mats,
    resolve_compute_dtype,
    resolve_scale,
)
from repro.jaxapi import fp8_operand_dtype, interpret_mode, shard_map
from repro.kernels import registry
from repro.kernels.ref import is_pow2
from repro.kernels.registry import QSPECS, get_backend, select_backend

__all__ = [
    "QuantEpilogue",
    "HadamardPlan",
    "QuantDotSpec",
    "RotationSpec",
    "plan_for",
    "make_plan",
    "hadamard",
    "quant_dot",
    "quant_dot_experts",
    "plan_cache_info",
]


@dataclasses.dataclass(frozen=True)
class QuantEpilogue:
    """Quantization epilogue applied to the rotated tensor before write-back.

    mode:      'int8' | 'fp8_e4m3' | 'fp8_e5m2'
    per_token: one symmetric absmax scale per (full-length) token row;
               False = one scale per tensor (never fusable: needs a
               global reduction, so it always runs as transform +
               XLA epilogue).
    dequant:   return the fake-quantized (quantize->dequantize) rotated
               tensor in the input dtype instead of ``(q, scales)`` --
               the training-path form consumed by fake-quant matmuls.
    """

    mode: str
    per_token: bool = True
    dequant: bool = False

    def __post_init__(self):
        if self.mode not in QSPECS:
            raise ValueError(
                f"unknown quantization mode {self.mode!r}; "
                f"expected one of {sorted(QSPECS)}"
            )


@dataclasses.dataclass(frozen=True)
class HadamardPlan:
    """Everything shape-dependent about one Hadamard configuration,
    computed once and cached. Hashable (the stacked base matrices are
    excluded from eq/hash), so jitted implementations take the plan as a
    static argument and XLA caches per plan."""

    n: int                           # full last-axis size
    p: int                           # per-group pow2 transform size (== n when pow2)
    dtype: str                       # canonical input/output dtype name
    compute_dtype: str               # dtype the matmul passes run in (f32
                                     # accumulation always; see
                                     # hadamard.resolve_compute_dtype)
    backend: str                     # resolved registry backend name
    scale: Optional[float]           # numeric scale folded into pass 0 (None = +-1)
    epilogue: Optional[QuantEpilogue]
    block_m: Optional[int]           # VMEM row tile (None = per-call heuristic)
    mesh_axes: Optional[Tuple[str, ...]] = None
                                     # mesh axes the quant_dot weight's
                                     # out-channel dim is sharded over --
                                     # part of the cache key, so plans
                                     # built under different meshes never
                                     # alias; None = single-device plan
    mats: np.ndarray = dataclasses.field(repr=False, compare=False, default=None)

    @property
    def grouped(self) -> bool:
        return self.p != self.n

    @property
    def num_passes(self) -> int:
        return 0 if self.p == 1 else int(self.mats.shape[0])


@functools.lru_cache(maxsize=None)
def _build_plan(n, p, dtype_name, compute_dtype, scale_val, backend, epilogue,
                block_m, mesh_axes=None):
    if p == 1:
        mats = np.ones((1, 1, 1), np.float32)
    else:
        mats = pack_pass_mats(base_matrices_np(p, scale_val))
    return HadamardPlan(
        n=n, p=p, dtype=dtype_name, compute_dtype=compute_dtype,
        backend=backend, scale=scale_val, epilogue=epilogue, block_m=block_m,
        mesh_axes=mesh_axes, mats=mats,
    )


def plan_for(
    n: int,
    *,
    dtype: Any = jnp.float32,
    scale: Union[str, float, None] = "ortho",
    backend: Optional[str] = None,
    epilogue: Optional[QuantEpilogue] = None,
    block_m: Optional[int] = None,
    compute_dtype: Any = None,
    mesh_axes: Optional[Tuple[str, ...]] = None,
) -> HadamardPlan:
    """Build (or fetch from the cache) the plan for an n-point transform.

    ``backend=None`` resolves via the registry: ``REPRO_HADAMARD_BACKEND``
    env override first, then auto-selection by size/platform. Non-power-
    of-2 ``n`` plans the grouped transform on the largest power-of-2
    divisor. ``compute_dtype=None`` resolves the dtype the matmul passes
    run in: native bf16/fp16 passes with f32 MXU accumulation for 16-bit
    inputs, f32 otherwise (explicitly overridable). ``mesh_axes`` marks
    a quant_dot plan as sharded over those mesh axes (the out-channel dim
    of the weight); it is part of the cache key, so plans built under a
    mesh never alias single-device plans. Repeated calls with the same
    key return the *same* plan object, so downstream jit caches hit.
    """
    if n < 1:
        raise ValueError(f"Hadamard size must be >= 1, got {n}")
    p = n if is_pow2(n) else largest_pow2_divisor(n)
    scale_val = resolve_scale(scale, p)
    resolved = select_backend(p, backend)
    return _build_plan(
        n, p, jnp.dtype(dtype).name,
        resolve_compute_dtype(dtype, compute_dtype), scale_val, resolved,
        epilogue, block_m, mesh_axes
    )


# Alias: ISSUE/API docs name both; plan_for reads better at call sites.
make_plan = plan_for


def plan_cache_info():
    """Plan-cache statistics (functools.lru_cache CacheInfo)."""
    return _build_plan.cache_info()


def _strip(plan: HadamardPlan) -> HadamardPlan:
    """The epilogue-free twin of a plan (used by fallbacks and pullbacks).
    Mesh axes are dropped too: the plain transform never shards."""
    if plan.epilogue is None and plan.mesh_axes is None:
        return plan
    return _build_plan(
        plan.n, plan.p, plan.dtype, plan.compute_dtype, plan.scale,
        plan.backend, None, plan.block_m
    )


# -------------------------------------------------------------- dispatch
def _group(x: jnp.ndarray, plan: HadamardPlan) -> jnp.ndarray:
    return x.reshape(*x.shape[:-1], plan.n // plan.p, plan.p)


def _row_axes(mesh, m: int, exclude=()) -> Tuple[str, ...]:
    """Mesh axes to split m rows over: every axis not in ``exclude``
    whose running size divides m, in mesh order (size-1 axes are kept --
    the spec stays row-sharded and costs nothing)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes, total = [], 1
    for a in mesh.axis_names:
        if a not in exclude and m % (total * sizes[a]) == 0:
            axes.append(a)
            total *= sizes[a]
    return tuple(axes)


def _on_rows(be, fn, x, n: int, *operands, cols=None):
    """Run ``fn(rows, *operands)``, a kernel of backend ``be`` over the
    rows of ``x`` (its leading dims), under the active sharding-rules
    mesh -- the one place that decides how a kernel call splits over a
    mesh. Pallas (Mosaic) kernels cannot be partitioned by GSPMD, so with
    more than one device ``shard_map`` splits the flattened rows over the
    mesh axes ``_row_axes`` picks. ``cols`` names the mesh axes that
    split the last axis of every operand and of the output (a weight's
    out-channel shards), and the rows then split over the other axes.
    Without ``cols`` every device gets the operands whole, which is
    counted as ``_sharded_fallback("replicated_operand")``: a weight the
    mesh holds sharded is gathered on every call. Every output keeps the
    row axis first. Without ``cols``, backends that partition natively,
    and single-device runs, call ``fn`` directly."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import current_mesh, sharding_rules

    mesh = current_mesh()
    if cols is None and (not be.mosaic or mesh is None
                         or mesh.devices.size == 1):
        return fn(x, *operands)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, n)
    rows = _row_axes(mesh, x2.shape[0], cols or ()) or None
    if cols:
        c = cols if len(cols) > 1 else cols[0]
        in_specs = (P(rows, None),) + (P(None, c),) * len(operands)
        out_specs = P(rows, c)
    else:
        if operands:
            _sharded_fallback(
                "replicated_operand",
                f"a {be.name} kernel over the rows of a "
                f"{mesh.devices.shape} mesh gets its weight whole on every "
                "device (no out-channel mesh axes: the plan has none, or "
                "they do not divide the weight); a weight stored sharded "
                "is gathered on every call")
        in_specs = (P(rows, None),) + (P(),) * len(operands)
        out_specs = P(rows, None)

    def local(*args):
        # the sharding-rules mesh is cleared inside, so nothing below
        # re-shards or constrains
        with sharding_rules(None):
            return fn(*args)

    out = shard_map(local, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs)(x2, *operands)
    return jax.tree.map(lambda o: o.reshape(*lead, o.shape[-1]), out)


def _dispatch_transform(x, plan: HadamardPlan, interpret: bool):
    if plan.p == 1:
        return x if plan.scale is None else x * jnp.asarray(plan.scale, x.dtype)
    be = get_backend(plan.backend)
    xg = _group(x, plan) if plan.grouped else x
    y = _on_rows(be, lambda r: be.transform(r, plan, interpret), xg, plan.p)
    return y.reshape(x.shape)


def _apply_epilogue_xla(y, epi: QuantEpilogue, out_dtype):
    """Reference epilogue on an already-rotated tensor (used when the
    backend has no fused path, for per-tensor scales, and for grouped
    transforms where the scale must span the full token row). Shares
    ``registry._quantize_rows`` with the fused kernels so numerics agree
    bit-for-bit."""
    q, s = registry._quantize_rows(
        y.astype(jnp.float32), epi.mode, axis=-1 if epi.per_token else None)
    if epi.dequant:
        return registry._dequantize(q, s, epi.mode).astype(out_dtype)
    return q.astype(QSPECS[epi.mode][1]), s


def _fusable(plan: HadamardPlan) -> bool:
    be = get_backend(plan.backend)
    return (
        not plan.grouped
        and plan.p > 1
        and plan.epilogue.per_token
        and be.fused is not None
        and be.supports(plan.p)
    )


def _dispatch_fused(x, plan: HadamardPlan, interpret: bool):
    if _fusable(plan):
        be = get_backend(plan.backend)
        return _on_rows(be, lambda r: be.fused(r, plan, interpret), x,
                        plan.p)
    y = _dispatch_transform(x, _strip(plan), interpret)
    return _apply_epilogue_xla(y, plan.epilogue, x.dtype)


def _dispatch_fused_dequant(x, plan: HadamardPlan, interpret: bool):
    if _fusable(plan):
        be = get_backend(plan.backend)
        return _on_rows(be, lambda r: be.fused_dequant(r, plan, interpret),
                        x, plan.p)
    y = _dispatch_transform(x, _strip(plan), interpret)
    return _apply_epilogue_xla(y, plan.epilogue, x.dtype)


# -------------------------------------------------------------- autodiff
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _transform(x, plan: HadamardPlan, interpret: bool):
    return _dispatch_transform(x, plan, interpret)


def _transform_fwd(x, plan, interpret):
    return _dispatch_transform(x, plan, interpret), None


def _transform_bwd(plan, interpret, _res, g):
    # H^T = H and the scale is scalar: the op is self-adjoint.
    return (_dispatch_transform(g, plan, interpret),)


_transform.defvjp(_transform_fwd, _transform_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fused(x, plan: HadamardPlan, interpret: bool):
    return _dispatch_fused(x, plan, interpret)


def _fused_fwd(x, plan, interpret):
    q, s = _dispatch_fused(x, plan, interpret)
    return (q, s), s


def _fused_bwd(plan, interpret, s, ct):
    """Straight-through: q = had(x)/s with s treated as a statistic, so
    the pullback of gq is had(gq)/s and the scale branch contributes
    nothing. int8 outputs are integer-typed (float0 cotangent): their
    quantized branch is non-differentiable by construction -- use
    ``QuantEpilogue(dequant=True)`` for the training path."""
    gq, _gs = ct
    if gq.dtype == float0:
        return (jnp.zeros(gq.shape, jnp.dtype(plan.dtype)),)
    gy = gq.astype(jnp.float32) / s
    gx = _dispatch_transform(gy, _strip(plan), interpret)
    return (gx.astype(jnp.dtype(plan.dtype)),)


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fused_dequant(x, plan: HadamardPlan, interpret: bool):
    return _dispatch_fused_dequant(x, plan, interpret)


def _fused_dequant_fwd(x, plan, interpret):
    return _dispatch_fused_dequant(x, plan, interpret), None


def _fused_dequant_bwd(plan, interpret, _res, g):
    # Straight-through on quantize-dequantize: the op behaves as the plain
    # rotation in the backward pass (NOT the raw fake-quant grad, whose
    # round() is zero a.e. -- see module docstring).
    return (_dispatch_transform(g, _strip(plan), interpret),)


_fused_dequant.defvjp(_fused_dequant_fwd, _fused_dequant_bwd)


# ----------------------------------------------------------- entry point
_UNSET = object()  # distinguishes "not passed" from an explicit default


def hadamard(
    x: jnp.ndarray,
    plan: Optional[HadamardPlan] = None,
    *,
    scale: Union[str, float, None] = _UNSET,
    backend: Optional[str] = _UNSET,
    epilogue: Optional[QuantEpilogue] = _UNSET,
    block_m: Optional[int] = _UNSET,
    compute_dtype: Any = _UNSET,
    interpret: Optional[bool] = None,
) -> Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Walsh-Hadamard transform of the last axis -- THE entry point.

    With ``plan=None`` a plan is built (and cached) from the keyword
    arguments and ``x``'s shape/dtype; passing an explicit plan skips all
    per-call decisions (plan-configuration keywords may then not be
    passed -- the plan already pins them, and silently ignoring a
    conflicting ``epilogue=...`` would change the return type). Returns
    the rotated tensor, or ``(q, scales)`` when the plan carries a
    :class:`QuantEpilogue` (the fake-quantized tensor when the epilogue
    has ``dequant=True``).

    ``interpret=None`` compiles the Pallas kernels on a TPU and runs them
    in interpret mode on the CPU (``jaxapi.interpret_mode``), so CPU
    tests validate the same kernel code path.
    """
    n = x.shape[-1]
    if plan is None:
        plan = plan_for(
            n, dtype=x.dtype,
            scale="ortho" if scale is _UNSET else scale,
            backend=None if backend is _UNSET else backend,
            epilogue=None if epilogue is _UNSET else epilogue,
            block_m=None if block_m is _UNSET else block_m,
            compute_dtype=None if compute_dtype is _UNSET else compute_dtype,
        )
    else:
        passed = [name for name, v in (("scale", scale), ("backend", backend),
                                       ("epilogue", epilogue),
                                       ("block_m", block_m),
                                       ("compute_dtype", compute_dtype))
                  if v is not _UNSET]
        if passed:
            raise ValueError(
                f"hadamard() got both an explicit plan and {passed}; plan "
                "configuration is fixed at plan_for() time"
            )
        if plan.n != n:
            raise ValueError(
                f"plan was built for n={plan.n} but x has last axis {n}"
            )
        if jnp.dtype(plan.dtype) != x.dtype:
            raise ValueError(
                f"plan was built for dtype {plan.dtype} but x is {x.dtype.name}; "
                "build a plan with plan_for(n, dtype=x.dtype, ...)"
            )
    if interpret is None:
        interpret = interpret_mode()
    if plan.epilogue is None:
        return _transform(x, plan, interpret)
    if plan.epilogue.dequant:
        return _fused_dequant(x, plan, interpret)
    return _fused(x, plan, interpret)


# ------------------------------------------------- fused quantized GEMM
def _qd_fusable(plan: HadamardPlan) -> bool:
    """Can the rotate+quantize+dot run as the backend's single kernel?
    Mirrors ``_fusable`` plus the backend must host a ``quant_dot`` and
    the minimal (p, 128) weight tile, charged as ``quant_dot_blocks``
    charges it (``_weight_tile_bytes``), must fit the kernel's VMEM
    budget. An oversize plan takes the unfused path instead of
    launching an over-budget kernel -- warned once per process and
    counted in ``TRACE_COUNTS[("quant_dot", "vmem_unfused")]``."""
    from repro.kernels.quant_dot import _weight_tile_bytes

    be = get_backend(plan.backend)
    kernel_ok = (
        not plan.grouped
        and plan.p > 1
        and plan.epilogue.per_token
        and getattr(be, "quant_dot", None) is not None
        and be.supports(plan.p)
    )
    if kernel_ok and (_weight_tile_bytes(plan.p, 128, plan.epilogue.mode)
                      > registry._VMEM_BUDGET_BYTES):
        registry.warn_once(
            ("quant_dot", "vmem_unfused"),
            f"the n={plan.p} {plan.epilogue.mode} quant_dot weight tile "
            "exceeds the kernel's VMEM budget; the site runs unfused "
            "(warned once per process; TRACE_COUNTS[('quant_dot', "
            "'vmem_unfused')] keeps counting)")
        return False
    return kernel_ok


def _resolve_mesh_axes(weight_axes, d: Optional[int]):
    """Resolve a weight's logical out-channel axis -> concrete mesh axes
    for the sharded quant_dot dispatch. Returns None (single-device plan)
    when no mesh is active, the logical axis maps to nothing, the mapped
    axes' total size is 1, or ``d`` is not divisible by it (the same
    divisibility guard ``distributed.sharding.constrain`` applies)."""
    if not weight_axes or d is None:
        return None
    from repro.distributed.sharding import _resolve_axis, current_mesh

    mesh = current_mesh()
    if mesh is None:
        return None
    ax = _resolve_axis(mesh, weight_axes[-1])
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for a in axes:
        total *= sizes[a]
    if total <= 1 or d % total:
        return None
    return axes


# Trace-time record of the last sharded dispatch decision (row axes the
# activation was sharded over, whether the shard-local compute was the
# fused kernel, which backend ran it). Observability hook for tests --
# NOT an API.
_LAST_SHARDED_DISPATCH: dict = {}


def _sharded_fallback(reason: str, msg: str) -> None:
    """Record (and warn once per process per reason, via the shared
    ``registry.warn_once`` idiom) that a mesh plan fell back from the
    sharded/fused hot path. Sharded perf regressions -- a plan silently
    going replicated, or shard-local compute silently going unfused --
    used to be invisible; now they show up in
    ``TRACE_COUNTS[("sharded_quant_dot", reason)]`` and as a one-shot
    ``RuntimeWarning``."""
    registry.warn_once(
        ("sharded_quant_dot", reason),
        f"sharded quant_dot fallback [{reason}]: {msg} (warned once "
        "per process; TRACE_COUNTS[('sharded_quant_dot', "
        f"{reason!r})] keeps counting)")


def _strip_mesh(plan: HadamardPlan) -> HadamardPlan:
    """The single-device twin of a mesh plan (same backend/epilogue/
    tiling, mesh_axes=None) -- the plan the shard-local kernel runs."""
    if plan.mesh_axes is None:
        return plan
    return _build_plan(
        plan.n, plan.p, plan.dtype, plan.compute_dtype, plan.scale,
        plan.backend, plan.epilogue, plan.block_m)


def _sharded_quant_dot(x, wq, sw, plan: HadamardPlan, interpret: bool,
                       schedule=None):
    """quant_dot over a mesh via ``_on_rows``'s ``shard_map``, fused and
    data-parallel:

      * the activation is ROW-SHARDED over every mesh axis the weight
        does not use (divisibility-guarded, ``_row_axes``) -- each shard
        rotates and quantizes only its own rows, so transform work is
        data-parallel instead of replicated per shard;
      * the contraction axis is never split (the Hadamard spans it): each
        shard contracts against ITS slice of the weight columns with ITS
        slice of the per-out-channel scales, so per-shard weight scales
        are used end to end and the assembled result is bitwise the
        single-device int8 output;
      * the shard-local compute is the FUSED rotate-once Pallas kernel
        whenever the (mesh-stripped) plan fuses; otherwise the unfused
        oracle semantics run shard-locally (grouped sizes, per-tensor
        scales, xla backend -- counted + warned via
        ``_sharded_fallback("unfused_local")`` so the regression is
        observable).

    Returns None when the plan's mesh axes are not provided by the
    current mesh (caller falls back to the replicated single-device path
    and records ``mesh_mismatch``)."""
    from repro.distributed.sharding import current_mesh
    from repro.kernels.quant_dot import epilogue_dot

    mesh = current_mesh()
    if mesh is None or any(a not in mesh.axis_names for a in plan.mesh_axes):
        return None
    local_plan = _strip_mesh(plan)
    epi = plan.epilogue
    d = wq.shape[-1]
    be = get_backend(local_plan.backend)
    fused = _qd_fusable(local_plan) and be.quant_dot_fused
    _LAST_SHARDED_DISPATCH.update(
        fused=fused, mesh_axes=plan.mesh_axes, backend=local_plan.backend,
        row_axes=_row_axes(mesh, math.prod(x.shape[:-1]), plan.mesh_axes))
    if fused:
        def local(xl, wl, sl):
            # the fused kernel, shard-local: xl is this shard's rows,
            # wl/sl its weight columns + scales; the grid schedule
            # (rotate_once / revisit / streamed DMA ring) applies
            # per shard unchanged
            return be.quant_dot(xl, wl, sl, local_plan, interpret,
                                schedule)
    else:
        _sharded_fallback(
            "unfused_local",
            f"shard-local compute for the n={plan.n} {epi.mode} plan runs "
            f"the unfused oracle (backend {local_plan.backend!r}, "
            f"grouped={plan.grouped}); the fused rotate-once kernel "
            "requires the pallas backend, a power-of-2 size within the "
            "kernel cap, and per-token scales")

        def local(xl, wl, sl):
            # the unfused oracle, shard-local: factored rotate (grouped
            # sizes included), per-token quantize of the FULL row, then
            # the shared epilogue-dot contraction
            y = _dispatch_transform(xl, _strip(local_plan), interpret)
            q, s = registry._quantize_rows(y.astype(jnp.float32), epi.mode)
            return epilogue_dot(q, s, wl, sl, epi.mode, jnp.dtype(plan.dtype))

    return _on_rows(be, local, x, plan.n, wq,
                    sw.reshape(1, d).astype(jnp.float32),
                    cols=plan.mesh_axes)


def _dispatch_quant_dot(x, wq, sw, plan: HadamardPlan, interpret: bool,
                        schedule=None):
    """rotate(x) -> per-token quantize -> contract against the offline-
    quantized weight (int8 w/ int32 accumulation, fp8 w/ f32), applying
    ``scale_x * scale_w`` in the epilogue. Mesh plans dispatch through
    shard_map -- row-sharded activations over the data axes, the weight's
    out-channel shards on its mesh axes, the fused rotate-once kernel
    shard-local; fused single-kernel when the plan supports it; otherwise
    the unfused oracle semantics (grouped transforms, per-tensor scales,
    backends without the kernel -- the pjit-shardable fallback).

    ``schedule`` picks the fused kernel's grid schedule (None defers to
    ``REPRO_QUANT_DOT_SCHEDULE``, then ``rotate_once``; ``"streamed"``
    double-buffers the weight DMA) and rides through the sharded
    dispatch to the shard-local kernel; the unfused oracle has no grid,
    so there it only validates."""
    if plan.mesh_axes and wq.ndim == 2 and plan.epilogue.per_token:
        out = _sharded_quant_dot(x, wq, sw, plan, interpret, schedule)
        if out is not None:
            return out
        _sharded_fallback(
            "mesh_mismatch",
            f"plan was built for mesh axes {plan.mesh_axes} but the "
            "current mesh does not provide them; quant_dot runs the "
            "replicated single-device path")
    elif plan.mesh_axes:
        _sharded_fallback(
            "unshardable_site",
            f"plan carries mesh axes {plan.mesh_axes} but the site "
            "cannot shard_map (needs a 2-D weight and per-token scales; "
            f"got wq.ndim={wq.ndim}, "
            f"per_token={plan.epilogue.per_token}); quant_dot runs the "
            "replicated single-device path")
    if _qd_fusable(plan):
        be = get_backend(plan.backend)
        return _on_rows(
            be, lambda r, w, sc: be.quant_dot(r, w, sc, plan, interpret,
                                              schedule),
            x, plan.n, wq, sw)
    from repro.kernels.quant_dot import epilogue_dot

    y = _dispatch_transform(x, _strip(plan), interpret)
    epi = plan.epilogue
    q, s = registry._quantize_rows(
        y.astype(jnp.float32), epi.mode, axis=-1 if epi.per_token else None)
    return epilogue_dot(q, s, wq, sw, epi.mode, jnp.dtype(plan.dtype))


def _dequant_weight(wq, sw):
    return wq.astype(jnp.float32) * sw


def _zero_cotangent(a):
    if jnp.issubdtype(a.dtype, jnp.integer):
        return np.zeros(a.shape, dtype=float0)
    return jnp.zeros(a.shape, a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _quant_dot_qw(x, wq, sw, plan: HadamardPlan, interpret: bool,
                  schedule=None):
    """Serving form: weights pre-quantized offline. Differentiable in x
    only (STE through the activation quantization); the quantized weight
    and its scales are statistics with zero pullback."""
    return _dispatch_quant_dot(x, wq, sw, plan, interpret, schedule)


def _quant_dot_qw_fwd(x, wq, sw, plan, interpret, schedule):
    return _dispatch_quant_dot(x, wq, sw, plan, interpret, schedule), (wq, sw)


def _quant_dot_qw_bwd(plan, interpret, schedule, res, g):
    # STE: out ~= had(x) @ W with W = dequant(wq, sw), so the x-pullback is
    # the (self-adjoint) rotation of g @ W^T.
    wq, sw = res
    W = _dequant_weight(wq, sw)
    gy = jnp.matmul(g.astype(jnp.float32), W.T,
                    preferred_element_type=jnp.float32)
    gx = _dispatch_transform(
        gy.astype(jnp.dtype(plan.dtype)), _strip(plan), interpret)
    return gx, _zero_cotangent(wq), _zero_cotangent(sw)


_quant_dot_qw.defvjp(_quant_dot_qw_fwd, _quant_dot_qw_bwd)


def _abft_quant_dot_impl(x, wq, sw, cw, plan, interpret, schedule):
    """Checksum-verified serving quant_dot (``repro.verify``, DESIGN.md
    section 14). Fused backends emit the per-row checksum residual from
    INSIDE the pallas_call (the verified kernel's real output is graph-
    identical to the unverified one); non-fused paths run the normal
    dispatch and derive the residual from the XLA oracle recompute.
    Rows whose residual exceeds the calibrated tolerance are poisoned
    with NaN -- an exact ``where`` select, so a healthy run is BITWISE
    identical to ABFT-off -- and surface at the serving step's logits
    guard, which retires the slot instead of emitting corrupt tokens."""
    from repro import verify
    from repro.kernels.quant_dot import xla_quant_dot_resid

    registry.TRACE_COUNTS[("abft", "quant_dot_site")] += 1
    be = get_backend(plan.backend)
    if _qd_fusable(plan) and be.quant_dot_fused:
        y, resid = _on_rows(
            be, lambda r, w, sc, c: be.quant_dot(r, w, sc, plan, interpret,
                                                 schedule, check=c),
            x, plan.n, wq, sw, cw)
    else:
        y = _dispatch_quant_dot(x, wq, sw, plan, interpret, schedule)
        resid = xla_quant_dot_resid(x, wq, sw, cw, plan, interpret)
    ok = verify.residual_ok(y, resid, n=wq.shape[0], d=wq.shape[-1])
    return jnp.where(ok, y, jnp.asarray(jnp.nan, y.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _quant_dot_qw_abft(x, wq, sw, cw, plan: HadamardPlan, interpret: bool,
                       schedule=None):
    """ABFT twin of ``_quant_dot_qw``: same serving semantics plus the
    column-checksum verification of ``_abft_quant_dot_impl``. The
    checksum vector ``cw`` is a statistic of the weight (zero pullback,
    like ``wq``/``sw``); the backward pass is the identical STE."""
    return _abft_quant_dot_impl(x, wq, sw, cw, plan, interpret, schedule)


def _quant_dot_qw_abft_fwd(x, wq, sw, cw, plan, interpret, schedule):
    return (_abft_quant_dot_impl(x, wq, sw, cw, plan, interpret, schedule),
            (wq, sw, cw))


def _quant_dot_qw_abft_bwd(plan, interpret, schedule, res, g):
    wq, sw, cw = res
    W = _dequant_weight(wq, sw)
    gy = jnp.matmul(g.astype(jnp.float32), W.T,
                    preferred_element_type=jnp.float32)
    gx = _dispatch_transform(
        gy.astype(jnp.dtype(plan.dtype)), _strip(plan), interpret)
    return (gx, _zero_cotangent(wq), _zero_cotangent(sw),
            _zero_cotangent(cw))


_quant_dot_qw_abft.defvjp(_quant_dot_qw_abft_fwd, _quant_dot_qw_abft_bwd)


def _quant_dot_w_impl(x, w, plan: HadamardPlan, interpret: bool,
                      schedule=None):
    from repro.core.wquant import quantize_weight

    qt = quantize_weight(w, plan.epilogue.mode)
    return _dispatch_quant_dot(x, qt.q, qt.scale, plan, interpret, schedule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _quant_dot_w(x, w, plan: HadamardPlan, interpret: bool, schedule=None):
    """Training form: full-precision weight, quantized per out-channel on
    the fly. STE through BOTH quantizations: out ~= had(x) @ w in the
    backward pass, so both gradients flow (w's raw fake-quant grad would
    be zero a.e. -- see the module docstring)."""
    return _quant_dot_w_impl(x, w, plan, interpret, schedule)


def _quant_dot_w_fwd(x, w, plan, interpret, schedule):
    return _quant_dot_w_impl(x, w, plan, interpret, schedule), (x, w)


def _quant_dot_w_bwd(plan, interpret, schedule, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    gy = jnp.matmul(gf, w.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)
    gx = _dispatch_transform(
        gy.astype(jnp.dtype(plan.dtype)), _strip(plan), interpret)
    y = _dispatch_transform(x, _strip(plan), interpret)
    yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
    gw = jnp.matmul(yf.T, gf.reshape(-1, gf.shape[-1]),
                    preferred_element_type=jnp.float32)
    return gx, gw.astype(w.dtype)


_quant_dot_w.defvjp(_quant_dot_w_fwd, _quant_dot_w_bwd)


def quant_dot(
    x: jnp.ndarray,
    w: Union[jnp.ndarray, "QTensor", Tuple[jnp.ndarray, jnp.ndarray]],
    plan: Optional[HadamardPlan] = None,
    *,
    mode: str = _UNSET,
    scale: Union[str, float, None] = _UNSET,
    backend: Optional[str] = _UNSET,
    block_m: Optional[int] = _UNSET,
    compute_dtype: Any = _UNSET,
    weight_axes: Optional[Tuple] = _UNSET,
    interpret: Optional[bool] = None,
    schedule: Optional[str] = None,
) -> jnp.ndarray:
    """``quantize(hadamard(x)) @ quantize(w)`` as ONE fused consumer path.

    The quantized hot path end to end: the row block is rotated, per-token
    quantized, and immediately contracted against the offline-quantized
    weight tile inside the same kernel (int8 operands with int32 MXU
    accumulation; fp8 operands multiplied exactly in bf16 with f32
    accumulation), with ``scale_x * scale_w`` applied in the epilogue --
    the rotated/quantized activations never round-trip through HBM.

    ``w`` is either the full-precision weight ``(n, d)`` (quantized per
    out-channel on the fly; differentiable in both operands via the
    straight-through estimator) or a pre-quantized
    :class:`repro.core.wquant.QTensor` (legacy ``(wq, sw)`` tuples are
    still accepted) from :func:`repro.core.wquant.quantize_weight` -- the
    serving form; differentiable in ``x`` only.

    ``weight_axes`` (the weight's logical sharding axes, e.g.
    ``("dff", "fsdp")``) makes the call mesh-aware: under an active
    sharding-rules mesh the out-channel axis resolves to concrete mesh
    axes, the plan is keyed on them, and dispatch goes through
    ``shard_map`` with per-shard weight scales (the xla backend as the
    shard-local oracle). Without a mesh this is a no-op.

    Plans must carry a non-dequant :class:`QuantEpilogue`; ``plan=None``
    builds one from ``mode`` (default ``"int8"``). Grouped (non-power-of-
    2) sizes and per-tensor scales fall back to the unfused oracle
    semantics -- same math, separate XLA ops, pjit-shardable.

    ``schedule`` selects the fused kernel's grid schedule
    (``"rotate_once"`` / ``"revisit"`` / ``"streamed"``; ``None`` defers
    to ``REPRO_QUANT_DOT_SCHEDULE``). It is a dispatch-level knob, not
    plan configuration: every schedule is bitwise-identical, so it may
    be passed alongside an explicit plan. ``"streamed"`` double-buffers
    the weight-tile DMA against the contraction; under interpret mode it
    falls back to ``rotate_once`` (warn-once) unless
    ``REPRO_QUANT_DOT_STREAM_INTERPRET=1``.
    """
    from repro.core.wquant import QTensor

    n = x.shape[-1]
    if isinstance(w, QTensor):
        w = (w.q, w.scale)
    if plan is None:
        d_out = w[0].shape[-1] if isinstance(w, tuple) else w.shape[-1]
        plan = plan_for(
            n, dtype=x.dtype,
            scale="ortho" if scale is _UNSET else scale,
            backend=None if backend is _UNSET else backend,
            epilogue=QuantEpilogue("int8" if mode is _UNSET else mode),
            block_m=None if block_m is _UNSET else block_m,
            compute_dtype=None if compute_dtype is _UNSET else compute_dtype,
            mesh_axes=_resolve_mesh_axes(
                None if weight_axes is _UNSET else weight_axes, d_out),
        )
    else:
        passed = [name for name, v in (("mode", mode), ("scale", scale),
                                       ("backend", backend),
                                       ("block_m", block_m),
                                       ("compute_dtype", compute_dtype),
                                       ("weight_axes", weight_axes))
                  if v is not _UNSET]
        if passed:
            raise ValueError(
                f"quant_dot() got both an explicit plan and {passed}; plan "
                "configuration is fixed at plan_for() time"
            )
        if plan.n != n:
            raise ValueError(
                f"plan was built for n={plan.n} but x has last axis {n}")
        if jnp.dtype(plan.dtype) != x.dtype:
            raise ValueError(
                f"plan was built for dtype {plan.dtype} but x is "
                f"{x.dtype.name}; build a plan with plan_for(n, "
                "dtype=x.dtype, ...)")
    if plan.epilogue is None or plan.epilogue.dequant:
        raise ValueError(
            "quant_dot requires a plan with a non-dequant QuantEpilogue "
            f"(got {plan.epilogue!r}); use plan_for(n, epilogue="
            "QuantEpilogue(mode))"
        )
    if interpret is None:
        interpret = interpret_mode()
    if isinstance(w, tuple):
        wq, sw = w
        if wq.shape[0] != n:
            raise ValueError(
                f"quantized weight has contraction dim {wq.shape[0]}, "
                f"expected {n}")
        want_dt = QSPECS[plan.epilogue.mode][1]
        if wq.dtype != want_dt:
            raise ValueError(
                f"pre-quantized weight dtype {wq.dtype.name} does not "
                f"match the plan's {plan.epilogue.mode!r} storage dtype "
                f"{jnp.dtype(want_dt).name}; quantize with "
                "wquant.quantize_weight(w, mode)")
        return _quant_dot_qw(x, wq, sw, plan, interpret, schedule)
    if w.shape[0] != n:
        raise ValueError(
            f"weight has contraction dim {w.shape[0]}, expected {n}")
    return _quant_dot_w(x, w, plan, interpret, schedule)


# ----------------------------------------------------- expert consumers
def _qd_experts_fusable(plan: HadamardPlan) -> bool:
    """Can the expert site run as the single 3-D rotate-once kernel?
    Needs everything ``_qd_fusable`` needs plus a backend hosting the
    expert kernel, and NO active mesh: under a mesh the expert einsum
    shards via GSPMD/pjit (a pallas_call would not partition), so the
    einsum form stays the sharded path -- counted (not warned: it is the
    designed mesh path, not a regression) in
    ``TRACE_COUNTS[("sharded_quant_dot", "experts_einsum_on_mesh")]``.

    Like every ``sharding_rules`` consumer (``constrain`` included), the
    mesh is read from the ambient context AT TRACE TIME: an outer jit
    traced off-mesh bakes the kernel form, one traced under the mesh
    bakes the einsum. Launchers key their step functions per mesh
    (``launch.steps``), so each mesh context traces its own executable."""
    from repro.distributed.sharding import current_mesh

    be = get_backend(plan.backend)
    kernel_ok = (_qd_fusable(plan)
                 and getattr(be, "quant_dot_experts", None) is not None)
    if kernel_ok and current_mesh() is not None:
        registry.TRACE_COUNTS[
            ("sharded_quant_dot", "experts_einsum_on_mesh")] += 1
        return False
    return kernel_ok


def _experts_einsum_qw(x, wq, sw, plan: HadamardPlan, interpret: bool):
    """The einsum form of the expert consumer: fused rotate+quantize
    kernel on the activation side ((q, scales) epilogue, one kernel --
    all experts share d_ff), then a real low-precision einsum per expert
    against PRE-quantized weights. The GSPMD-shardable path and the
    oracle the fused 3-D kernel is tested against. The scales factor out
    of the einsum exactly (s per token row, sw per
    (expert, out-channel))."""
    q, s = hadamard(x, plan, interpret=interpret)
    if QSPECS[plan.epilogue.mode][2]:
        acc = jnp.einsum("becf,efd->becd", q.astype(jnp.int8),
                         wq.astype(jnp.int8),
                         preferred_element_type=jnp.int32
                         ).astype(jnp.float32)
    else:
        odt = fp8_operand_dtype()
        acc = jnp.einsum("becf,efd->becd", q.astype(odt), wq.astype(odt),
                         preferred_element_type=jnp.float32)
    out = acc * s * sw[None]                            # (B,E,c,d)*(1,E,1,d)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _quant_dot_experts_qw(x, wq, sw, plan: HadamardPlan, interpret: bool,
                          schedule=None):
    """Serving form for stacked expert weights, PRE-quantized (zero
    per-forward weight quantization), differentiable in x only (STE).

    Dispatch: the single fused 3-D (expert, rows, out-channels)
    rotate-once kernel when the plan fuses off-mesh -- rotation,
    per-token quantize AND the per-expert contraction in ONE pallas_call,
    no HBM round trip of (q, scales); otherwise the einsum form
    (``_experts_einsum_qw``: grouped sizes, active meshes via GSPMD,
    backends without the expert kernel). ``schedule`` picks the fused
    kernel's grid schedule (``"streamed"`` = DMA-ring weight prefetch);
    the einsum form has no grid, so there it is ignored."""
    if _qd_experts_fusable(plan):
        return get_backend(plan.backend).quant_dot_experts(
            x, wq, sw, plan, interpret, schedule)
    return _experts_einsum_qw(x, wq, sw, plan, interpret)


def _qd_experts_qw_fwd(x, wq, sw, plan, interpret, schedule):
    return (_quant_dot_experts_qw(x, wq, sw, plan, interpret, schedule),
            (wq, sw))


def _qd_experts_qw_bwd(plan, interpret, schedule, res, g):
    # STE: out ~= had(x) @ W per expert with W = dequant(wq, sw); the
    # quantized weight and its scales are statistics with zero pullback.
    wq, sw = res
    W = wq.astype(jnp.float32) * sw                     # (E, f, d)
    gf = g.astype(jnp.float32)
    gy = jnp.einsum("becd,efd->becf", gf, W)
    gx = _dispatch_transform(
        gy.astype(jnp.dtype(plan.dtype)), _strip(plan), interpret)
    return gx, _zero_cotangent(wq), _zero_cotangent(sw)


_quant_dot_experts_qw.defvjp(_qd_experts_qw_fwd, _qd_experts_qw_bwd)


def _abft_quant_dot_experts_impl(x, wq, sw, cw, plan, interpret, schedule):
    """Checksum-verified expert consumer: the fused 3-D kernel emits a
    per-(expert, row) residual alongside the real output (DESIGN.md
    section 14); rows that fail verification are NaN-poisoned via an
    exact select (healthy runs stay bitwise identical to ABFT-off).
    Callers gate on ``_qd_experts_fusable`` -- the einsum form has no
    checksum output."""
    from repro import verify

    registry.TRACE_COUNTS[("abft", "quant_dot_experts_site")] += 1
    y, resid = get_backend(plan.backend).quant_dot_experts(
        x, wq, sw, plan, interpret, schedule, check=cw)
    ok = verify.residual_ok(y, resid, n=wq.shape[1], d=wq.shape[-1])
    return jnp.where(ok, y, jnp.asarray(jnp.nan, y.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _quant_dot_experts_qw_abft(x, wq, sw, cw, plan: HadamardPlan,
                               interpret: bool, schedule=None):
    """ABFT twin of ``_quant_dot_experts_qw`` (fused form only); ``cw``
    is a weight statistic with zero pullback, backward is the same STE."""
    return _abft_quant_dot_experts_impl(x, wq, sw, cw, plan, interpret,
                                        schedule)


def _qd_experts_qw_abft_fwd(x, wq, sw, cw, plan, interpret, schedule):
    return (_abft_quant_dot_experts_impl(x, wq, sw, cw, plan, interpret,
                                         schedule),
            (wq, sw, cw))


def _qd_experts_qw_abft_bwd(plan, interpret, schedule, res, g):
    wq, sw, cw = res
    W = wq.astype(jnp.float32) * sw                     # (E, f, d)
    gf = g.astype(jnp.float32)
    gy = jnp.einsum("becd,efd->becf", gf, W)
    gx = _dispatch_transform(
        gy.astype(jnp.dtype(plan.dtype)), _strip(plan), interpret)
    return (gx, _zero_cotangent(wq), _zero_cotangent(sw),
            _zero_cotangent(cw))


_quant_dot_experts_qw_abft.defvjp(_qd_experts_qw_abft_fwd,
                                  _qd_experts_qw_abft_bwd)


def _quant_dot_experts_w_impl(x, w, plan, interpret, schedule=None):
    from repro.core.wquant import quantize_weight

    qt = quantize_weight(w, plan.epilogue.mode)         # (E,f,d), (E,1,d)
    return _quant_dot_experts_qw(x, qt.q, qt.scale, plan, interpret,
                                 schedule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _quant_dot_experts_w(x, w, plan: HadamardPlan, interpret: bool,
                         schedule=None):
    """Training einsum form: full-precision expert weights, quantized per
    (expert, out-channel) on the fly. STE through BOTH quantizations."""
    return _quant_dot_experts_w_impl(x, w, plan, interpret, schedule)


def _qd_experts_w_fwd(x, w, plan, interpret, schedule):
    return _quant_dot_experts_w_impl(x, w, plan, interpret, schedule), (x, w)


def _qd_experts_w_bwd(plan, interpret, schedule, res, g):
    x, w = res
    stripped = _strip(plan)
    gf = g.astype(jnp.float32)
    gy = jnp.einsum("becd,efd->becf", gf, w.astype(jnp.float32))
    gx = hadamard(gy.astype(x.dtype), stripped, interpret=interpret)
    y = hadamard(x, stripped, interpret=interpret)
    gw = jnp.einsum("becf,becd->efd", y.astype(jnp.float32), gf)
    return gx, gw.astype(w.dtype)


_quant_dot_experts_w.defvjp(_qd_experts_w_fwd, _qd_experts_w_bwd)


def quant_dot_experts(x, w, plan: HadamardPlan,
                      interpret: Optional[bool] = None,
                      schedule: Optional[str] = None) -> jnp.ndarray:
    """Per-expert quant_dot: ``einsum('becf,efd->becd')`` semantics with
    the shared online Hadamard on the dispatched activations (all experts
    share d_ff) and real int8/fp8 expert weights with
    per-(expert, out-channel) scales. Off-mesh fusable plans run the
    single 3-D (expert, rows, out-channels) rotate-once Pallas kernel --
    rotation, quantize and every expert's contraction in ONE pallas_call;
    under a mesh (GSPMD shards the einsum) or for non-fusable plans the
    einsum form runs. ``w`` is the raw (E, f, d) weight (training; STE in
    both operands) or a pre-quantized QTensor (serving; x-only
    gradients)."""
    from repro.core.wquant import QTensor

    if interpret is None:
        interpret = interpret_mode()
    if isinstance(w, QTensor):
        return _quant_dot_experts_qw(x, w.q, w.scale, plan, interpret,
                                     schedule)
    return _quant_dot_experts_w(x, w, plan, interpret, schedule)


# --------------------------------------------- declarative rotation sites
def _cfg_backend_name(backend: str) -> Optional[str]:
    # "auto" defers to the registry (env override, then size/platform).
    return None if backend == "auto" else backend


@dataclasses.dataclass(frozen=True)
class RotationSpec:
    """A declarative activation-only rotation site (DESIGN.md section 7):
    the attention Q/K/V pre-quantization hook, built once from the model
    config instead of threading a ``QuantConfig`` into free functions.

    n:         transform size (the per-head dim at the QK sites)
    mode:      'none' (no quantization) | 'int8' | 'fp8_e4m3' | 'fp8_e5m2'
    rotate:    apply the online Hadamard (False = quantize-only site, the
               V path: its rotation is fused offline into (W_v, W_o))
    dequant:   return the fake-quantized tensor (the KV-cache form) --
               ``(q, scales)`` when False
    Calling the spec on a tensor dispatches through the cached plan: the
    rotate+quantize site runs as ONE fused kernel when the plan fuses.
    """

    n: int
    mode: str = "none"
    rotate: bool = True
    per_token: bool = True
    dequant: bool = True
    scale: Union[str, float, None] = "ortho"
    backend: Optional[str] = None
    block_m: Optional[int] = None
    compute_dtype: Optional[str] = None
    abft: bool = False

    def __post_init__(self):
        if self.mode != "none" and self.mode not in QSPECS:
            raise ValueError(
                f"unknown quantization mode {self.mode!r}; expected 'none' "
                f"or one of {sorted(QSPECS)}")

    @classmethod
    def for_config(cls, n: int, cfg, *, rotate: Optional[bool] = None,
                   quantize: Optional[bool] = None,
                   per_token: bool = True) -> "RotationSpec":
        """Build the spec a QuantConfig implies for an n-point site.
        ``quantize`` defaults to the KV-site rule (cfg.enabled and
        cfg.kv_quant); ``rotate`` defaults to cfg.rotating."""
        q = (cfg.enabled and cfg.kv_quant) if quantize is None else \
            (quantize and cfg.enabled)
        return cls(
            n=n, mode=cfg.mode if q else "none",
            rotate=cfg.rotating if rotate is None else rotate,
            per_token=per_token, backend=_cfg_backend_name(cfg.backend),
            abft=bool(getattr(cfg, "abft", False)))

    def plan(self, dtype) -> HadamardPlan:
        epi = None
        if self.mode != "none":
            epi = QuantEpilogue(self.mode, per_token=self.per_token,
                                dequant=self.dequant)
        return plan_for(
            self.n, dtype=dtype, scale=self.scale, backend=self.backend,
            epilogue=epi, block_m=self.block_m,
            compute_dtype=self.compute_dtype)

    def __call__(self, x: jnp.ndarray, interpret: Optional[bool] = None):
        if x.shape[-1] != self.n:
            raise ValueError(
                f"RotationSpec was built for n={self.n} but x has last "
                f"axis {x.shape[-1]}")
        if self.rotate:
            y = hadamard(x, self.plan(x.dtype), interpret=interpret)
            if self.mode == "none" and self._abft_verifying():
                # pure-rotation site: the transform-linearity invariant
                # (sum-of-outputs vs transform-of-sum) verifies the whole
                # batch for ~1/m of the site's cost; a failed check
                # NaN-poisons the site via an exact select, so healthy
                # runs stay bitwise identical to ABFT-off and the serving
                # logits guard attributes the trip (DESIGN.md section 14).
                from repro.core.hadamard import hadamard_check

                registry.TRACE_COUNTS[("abft", "rotation_site")] += 1
                ok = hadamard_check(x, y, scale=self.scale,
                                    compute_dtype=self.compute_dtype)
                y = jnp.where(ok, y, jnp.asarray(jnp.nan, y.dtype))
            return y
        if self.mode != "none":
            from repro.core.quant import quantize

            return quantize(x, self.mode,
                            axis=-1 if self.per_token else None)
        return x

    def _abft_verifying(self) -> bool:
        from repro.verify.abft import abft_enabled

        return self.abft or abft_enabled()


@dataclasses.dataclass(frozen=True)
class QuantDotSpec:
    """A declarative rotation-CONSUMER site: ``x @ w`` with the online
    Hadamard on x's contraction axis and low-precision operands, bound to
    a concrete weight with ``spec.bind(w)`` (DESIGN.md section 7).

    The spec pins everything about the site that is not the weight value:
    transform size, quantization mode ('none' = unquantized matmul),
    whether the site rotates, scale granularity, backend/tiling overrides,
    the fused kernel's grid ``schedule`` (``"streamed"`` = DMA-ring weight
    prefetch; ``None`` defers to the env/default),
    and the weight's LOGICAL sharding axes -- which make the bound call
    mesh-aware: under an active sharding-rules mesh the out-channel axis
    resolves to mesh axes, folds into the plan cache key, and dispatch
    goes through ``shard_map`` with per-shard weight scales.

    ``bind`` accepts either the raw full-precision weight (training: the
    weight is quantized per out-channel on the fly, differentiable in
    both operands via the STE) or a pre-quantized
    :class:`~repro.core.wquant.QTensor` (serving: the forward contracts
    against ``q`` directly -- ZERO per-forward weight quantization).
    """

    n: int
    mode: str = "int8"
    rotate: bool = True
    per_token: bool = True
    scale: Union[str, float, None] = "ortho"
    backend: Optional[str] = None
    block_m: Optional[int] = None
    compute_dtype: Optional[str] = None
    weight_axes: Optional[Tuple[Optional[str], ...]] = None
    schedule: Optional[str] = None
    abft: bool = False

    def __post_init__(self):
        if self.mode != "none" and self.mode not in QSPECS:
            raise ValueError(
                f"unknown quantization mode {self.mode!r}; expected 'none' "
                f"or one of {sorted(QSPECS)}")
        if self.schedule is not None:
            from repro.kernels.quant_dot import SCHEDULES

            if self.schedule not in SCHEDULES:
                raise ValueError(
                    f"unknown quant_dot schedule {self.schedule!r}; "
                    f"expected one of {SCHEDULES}")

    @classmethod
    def for_config(cls, n: int, cfg, *,
                   weight_axes: Optional[Tuple] = None) -> "QuantDotSpec":
        """The spec a QuantConfig implies for an n-point consumer site.
        ``cfg.schedule`` (when set) pins the fused-kernel grid schedule --
        the serving degradation ladder relies on this to re-warm one rung
        down without touching the env override."""
        return cls(n=n, mode=cfg.mode, rotate=cfg.rotating,
                   per_token=cfg.per_token,
                   backend=_cfg_backend_name(cfg.backend),
                   schedule=getattr(cfg, "schedule", None),
                   weight_axes=weight_axes,
                   abft=bool(getattr(cfg, "abft", False)))

    @property
    def quantizing(self) -> bool:
        return self.mode != "none"

    def plan(self, dtype, d: Optional[int] = None) -> HadamardPlan:
        """The (cached) quant_dot plan for io dtype ``dtype`` and weight
        out-channels ``d`` -- mesh axes resolved from the spec's logical
        weight axes against the CURRENT mesh, so the same spec yields
        distinct plan-cache entries on and off a mesh."""
        return plan_for(
            self.n, dtype=dtype, scale=self.scale, backend=self.backend,
            epilogue=QuantEpilogue(self.mode, per_token=self.per_token),
            block_m=self.block_m, compute_dtype=self.compute_dtype,
            mesh_axes=_resolve_mesh_axes(self.weight_axes, d))

    def _transform_plan(self, dtype) -> HadamardPlan:
        return plan_for(self.n, dtype=dtype, scale=self.scale,
                        backend=self.backend, block_m=self.block_m,
                        compute_dtype=self.compute_dtype)

    def _coerce_weight(self, w):
        """Normalize the bound weight: QTensor passes through; a legacy
        ``(wq, sw)`` pre-quantized tuple is wrapped into a QTensor in the
        spec's mode (validating the storage dtype); raw arrays return
        unchanged."""
        from repro.core.wquant import QTensor

        if isinstance(w, QTensor) or not isinstance(w, tuple):
            return w
        wq, sw = w
        if self.quantizing:
            want_dt = QSPECS[self.mode][1]
            if wq.dtype != want_dt:
                raise ValueError(
                    f"pre-quantized weight dtype {wq.dtype.name} does not "
                    f"match the spec's {self.mode!r} storage dtype "
                    f"{jnp.dtype(want_dt).name}; quantize with "
                    "wquant.quantize_weight(w, mode)")
        return QTensor(q=wq, scale=sw, mode=self.mode)

    # ------------------------------------------------------------- dense
    def bind(self, w, *, interpret: Optional[bool] = None):
        """Bind the site to a weight; returns ``fn(x) -> (..., d)``.
        ``w``: raw array (training), QTensor, or legacy ``(wq, sw)``."""
        from repro.core.wquant import QTensor

        w = self._coerce_weight(w)
        if isinstance(w, QTensor):
            return functools.partial(self._apply_qtensor, w, interpret)
        return functools.partial(self._apply_raw, w, interpret)

    def __call__(self, x, w, *, interpret: Optional[bool] = None):
        return self.bind(w, interpret=interpret)(x)

    def _abft_verifying(self, w) -> bool:
        """ABFT-verify this site? Needs BOTH the stored checksum (the
        weight was quantized under an abft config / ``REPRO_ABFT``) and
        the runtime switch -- checksums alone are inert metadata."""
        from repro.verify.abft import abft_enabled

        return getattr(w, "check", None) is not None and (
            self.abft or abft_enabled())

    def _apply_qtensor(self, w, interpret, x):
        if not self.quantizing or w.mode != self.mode:
            # storage-only weight at a site whose config does not consume
            # it natively: dequantize (NOT re-quantize) and run raw
            return self._apply_raw(w.dequant(x.dtype), interpret, x)
        if self.rotate:
            if interpret is None:
                interpret = interpret_mode()
            plan = self.plan(x.dtype, d=w.q.shape[-1])
            if self._abft_verifying(w):
                if plan.mesh_axes is None:
                    return _quant_dot_qw_abft(x, w.q, w.scale, w.check,
                                              plan, interpret,
                                              self.schedule)
                registry.warn_once(
                    ("abft", "sharded_fallback"),
                    "ABFT checksums are present but the plan shards over "
                    f"mesh axes {plan.mesh_axes}; the shard_map dispatch "
                    "has no checksum output, so this site runs UNVERIFIED")
            return _quant_dot_qw(x, w.q, w.scale, plan, interpret,
                                 self.schedule)
        # no rotation site: real quantized matmul, pre-quantized weight
        from repro.kernels.quant_dot import epilogue_dot

        q, s = registry._quantize_rows(
            x.astype(jnp.float32), self.mode,
            axis=-1 if self.per_token else None)
        return epilogue_dot(q, s, w.q, w.scale, self.mode, x.dtype)

    def _apply_raw(self, w, interpret, x):
        if not self.quantizing:
            if self.rotate:
                return hadamard(x, self._transform_plan(x.dtype),
                                interpret=interpret) @ w
            return x @ w
        if not self.rotate:
            # no rotation insertion point: the plain fake-quant matmul
            from repro.core.quant import QuantConfig
            from repro.core.quant import quant_dot as _fake_quant_dot

            return _fake_quant_dot(
                x, w, QuantConfig(mode=self.mode, per_token=self.per_token))
        plan = self.plan(x.dtype, d=w.shape[-1])
        if interpret is None:
            interpret = interpret_mode()
        return _quant_dot_w(x, w, plan, interpret, self.schedule)

    # ----------------------------------------------------------- experts
    def bind_experts(self, w, *, interpret: Optional[bool] = None):
        """Bind the MoE expert form (``'becf,efd->becd'`` semantics,
        stacked expert weights sharing one d_ff Hadamard); returns
        ``fn(x)``.

        Off-mesh, fusable plans run the single 3-D rotate-once Pallas
        kernel (one pallas_call for rotation + quantize + every expert's
        contraction). Under a mesh the einsum form runs instead and
        shards under GSPMD/pjit via the surrounding constraints (the
        shard_map dispatch is 2-D-only). ``weight_axes`` is carried as
        declarative metadata only at this site today."""
        from repro.core.wquant import QTensor

        w = self._coerce_weight(w)
        if isinstance(w, QTensor):
            return functools.partial(self._apply_experts_qtensor, w,
                                     interpret)
        return functools.partial(self._apply_experts_raw, w, interpret)

    def _apply_experts_qtensor(self, w, interpret, x):
        if not self.quantizing or w.mode != self.mode:
            return self._apply_experts_raw(w.dequant(x.dtype), interpret, x)
        if self.rotate:
            if self._abft_verifying(w):
                if interpret is None:
                    interpret = interpret_mode()
                plan = self.plan(x.dtype)
                if _qd_experts_fusable(plan):
                    return _quant_dot_experts_qw_abft(
                        x, w.q, w.scale, w.check, plan, interpret,
                        self.schedule)
                registry.warn_once(
                    ("abft", "experts_einsum_fallback"),
                    "ABFT checksums are present but the expert site runs "
                    "the einsum form (active mesh or non-fusable plan), "
                    "which has no checksum output; it runs UNVERIFIED")
            return quant_dot_experts(x, w, self.plan(x.dtype),
                                     interpret=interpret,
                                     schedule=self.schedule)
        from repro.core.quant import quantize

        xq = quantize(x, self.mode, axis=-1 if self.per_token else None)
        return jnp.einsum("becf,efd->becd", xq,
                          w.dequant(x.dtype)).astype(x.dtype)

    def _apply_experts_raw(self, w, interpret, x):
        if not self.quantizing:
            if self.rotate:
                xr = hadamard(x, self._transform_plan(x.dtype),
                              interpret=interpret)
                return jnp.einsum("becf,efd->becd", xr, w)
            return jnp.einsum("becf,efd->becd", x, w)
        if not self.rotate:
            from repro.core.quant import quantize

            xq = quantize(x, self.mode, axis=-1 if self.per_token else None)
            return jnp.einsum("becf,efd->becd", xq,
                              quantize(w, self.mode, axis=-2))
        return quant_dot_experts(x, w, self.plan(x.dtype),
                                 interpret=interpret,
                                 schedule=self.schedule)
