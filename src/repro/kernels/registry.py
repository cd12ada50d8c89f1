"""Backend registry for the plan-based Hadamard API (DESIGN.md section 5).

Every transform implementation is a *backend* registered here via the
``@register_backend`` decorator -- replacing the if/else string chains the
old entry points (``kernels.ops.hadamard``, ``core.rotations.
online_hadamard``) each carried their own copy of. A backend exposes:

  * ``transform(x, plan, interpret)``  -- rotate the last axis (== plan.p)
  * ``fused(x, plan, interpret)``      -- rotate + quantize epilogue in one
    kernel, returning ``(q, scales)``; ``None`` when the backend has no
    fused path (the dispatcher falls back to transform + XLA epilogue)
  * ``fused_dequant(x, plan, interpret)`` -- rotate + fake-quantize
    (quantize-dequantize) in one kernel; the training-path variant
  * ``supports(p)``   -- can this backend run a p-point transform?

Selection (``select_backend``): an explicit request wins when supported
(with the historical pallas -> xla fallback above the kernel size cap);
otherwise the ``REPRO_HADAMARD_BACKEND`` environment variable; otherwise
the highest-priority auto-selectable backend that supports the size on
this platform.  Registered backends:

  pallas -- the HadaCore Pallas TPU kernels (VMEM-resident multi-pass
            matmul; interpret mode off-TPU). Hosts the fused
            rotate+quantize kernel: the rotated row block is already in
            VMEM, so the per-token absmax and int8/fp8 cast happen before
            write-back and the quantized tensor is the only HBM output.
  xla    -- the MXU-factored pure-JAX path (shards trivially under pjit;
            no size cap).
  ref    -- the paper's Listing-1 scalar FWHT oracle (never auto-picked).
"""
from __future__ import annotations

import collections
import functools
import os
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hadamard import MXU_TILE, _apply_passes, unpack_pass_mats
from repro.kernels.ref import fwht

__all__ = [
    "Backend",
    "register_backend",
    "get_backend",
    "available_backends",
    "select_backend",
    "BACKEND_ENV_VAR",
    "MAX_KERNEL_SIZE",
    "default_block_m",
    "QSPECS",
    "TRACE_COUNTS",
    "warn_once",
    "WARN_ONCE_SEEN",
]

BACKEND_ENV_VAR = "REPRO_HADAMARD_BACKEND"

# Same per-invocation cap as the paper's kernel (2^15). Above this the
# (block_m, n) row tile would still fit VMEM only for tiny block_m.
MAX_KERNEL_SIZE = 32768

# VMEM the tile heuristics may fill with a kernel's counted residents:
# half of Mosaic's default 16 MiB scoped-VMEM limit on v5e, so the
# pipeline's second buffer of each blocked operand fits beside them.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# mode -> (grid max, storage dtype, integer grid?). The fused kernel and
# the XLA epilogue fallback share this table so all paths agree bit-for-bit.
QSPECS = {
    "int8": (127.0, jnp.int8, True),
    "fp8_e4m3": (448.0, jnp.float8_e4m3fn, False),
    "fp8_e5m2": (57344.0, jnp.float8_e5m2, False),
}

# (backend, kind) -> number of times the jitted implementation was TRACED
# (i.e. compiled). Plan-cache tests assert repeated same-shape calls do not
# grow these counters. The sharded quant_dot dispatcher also counts its
# trace-time fallback decisions here under ("sharded_quant_dot", <reason>)
# keys -- see ``core.api._sharded_fallback`` -- so a mesh plan silently
# losing the fused/sharded hot path is observable in tests and debugging.
TRACE_COUNTS: collections.Counter = collections.Counter()

# Keys already warned about via ``warn_once`` -- one warning per process
# per key, while the companion TRACE_COUNTS entry keeps counting every
# occurrence. Tests reset individual keys with ``WARN_ONCE_SEEN.discard``
# (never the counter).
WARN_ONCE_SEEN: set = set()


def warn_once(key: Tuple[str, str], msg: str, *,
              category=RuntimeWarning, stacklevel: int = 3,
              count: bool = True) -> None:
    """THE warn-once-with-counter idiom (previously copied by the
    quant_dot stream fallback, ``core.api._sharded_fallback``, and the
    ops/fused_quant/rotations deprecation shims): emit ``msg`` as a
    one-shot warning per process per ``key`` and tick
    ``TRACE_COUNTS[key]`` on EVERY call, so the fallback/deprecation
    stays observable after the warning goes quiet."""
    if count:
        TRACE_COUNTS[key] += 1
    if key not in WARN_ONCE_SEEN:
        WARN_ONCE_SEEN.add(key)
        warnings.warn(msg, category, stacklevel=stacklevel)


def _epilogue_out_bytes_per_row(n: int, in_itemsize: int, epilogue) -> int:
    """HBM-output bytes one row contributes inside the kernel's VMEM tile.

    * no epilogue        -> the rotated row in the io dtype
    * (q, scales) form   -> the quantized row + one f32 scale
    * dequant form       -> the fake-quantized row in the io dtype
    """
    if epilogue is None or epilogue.dequant:
        return n * in_itemsize
    q_itemsize = jnp.dtype(QSPECS[epilogue.mode][1]).itemsize
    return n * q_itemsize + 4


def default_block_m(n: int, m: int, dtype=jnp.float32, *,
                    compute_dtype=None, epilogue=None) -> int:
    """Rows per grid step. Plays the role of the paper's empirically chosen
    warps_per_block x num_chunks: large enough to keep the MXU busy
    (>=128-row matmuls when possible), small enough that the ACTUAL VMEM
    residents fit the budget: the input tile, the compute-dtype working
    copy (bf16/fp16 plans skip the old unconditional f32 upcast, so
    16-bit inputs get ~2x larger row tiles), and every epilogue output
    (the fused kernels' q tile + per-row scales used to go uncharged,
    overshooting the budget the docstring promises for large n)."""
    in_b = jnp.dtype(dtype).itemsize
    cb = jnp.dtype(compute_dtype).itemsize if compute_dtype is not None else 4
    bytes_per_row = n * (in_b + cb) + _epilogue_out_bytes_per_row(
        n, in_b, epilogue)
    bm = max(8, _VMEM_BUDGET_BYTES // max(bytes_per_row, 1))
    bm = min(bm, 256, m)
    # round down to the sublane multiple of the io dtype; keep one sublane
    sub = 16 if in_b == 2 else 8
    return max(sub, (bm // sub) * sub)


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, "Backend"] = {}


def register_backend(cls):
    """Class decorator: instantiate and register a backend under its name."""
    inst = cls()
    _REGISTRY[inst.name] = inst
    return cls


def get_backend(name: str) -> "Backend":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown Hadamard backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    """Registered backend names, highest selection priority first."""
    return tuple(sorted(_REGISTRY, key=lambda k: -_REGISTRY[k].priority))


def select_backend(p: int, requested: Optional[str] = None) -> str:
    """Resolve the backend for a p-point transform.

    Explicit request > ``REPRO_HADAMARD_BACKEND`` env var > auto (priority
    order over backends whose ``supports(p)`` holds). A requested backend
    that cannot run the size falls through to auto selection (e.g.
    ``backend="pallas"`` above the kernel cap runs on XLA) -- warned once
    per process and counted in
    ``TRACE_COUNTS[("backend_fallback", <requested>)]`` on every plan
    build, so a kernel that silently stopped running shows up.
    """
    if requested in (None, "auto"):
        requested = os.environ.get(BACKEND_ENV_VAR) or None
    if requested is not None:
        be = get_backend(requested)  # raises on unknown names
        if be.supports(p):
            return be.name
    for name in available_backends():
        be = _REGISTRY[name]
        if be.auto and be.supports(p):
            if requested is not None:
                warn_once(
                    ("backend_fallback", requested),
                    f"Hadamard backend {requested!r} cannot run a {p}-point "
                    f"transform; using {name!r} (warned once per process; "
                    f"TRACE_COUNTS[('backend_fallback', {requested!r})] "
                    "keeps counting)")
            return name
    raise ValueError(f"no registered backend supports a {p}-point transform")


class Backend:
    """Base class: a named transform implementation with optional fused
    rotate+quantize paths. Subclasses are registered via
    ``@register_backend`` and selected by ``select_backend``."""

    name: str = "?"
    priority: int = 0
    auto: bool = True  # eligible for automatic selection

    def supports(self, p: int) -> bool:
        raise NotImplementedError

    def transform(self, x, plan, interpret: bool):
        raise NotImplementedError

    # Optional single-kernel epilogue paths (None = dispatcher falls back
    # to transform + XLA epilogue).
    fused = None
    fused_dequant = None
    # Optional rotate+quantize+GEMM consumer path (None = dispatcher falls
    # back to transform + shared unfused epilogue-dot math).
    quant_dot = None
    # Optional fused consumer for stacked (E, n, d) expert weights (the
    # 3-D rotate-once grid); None = per-expert einsum fallback.
    quant_dot_experts = None
    # Does ``quant_dot`` run as ONE kernel (rotation, quantize and GEMM
    # fused)? False means the hosted quant_dot is the unfused oracle
    # semantics (xla) -- the sharded dispatcher uses this to warn when a
    # mesh plan silently loses the fused hot path.
    quant_dot_fused = False
    # Are the kernels Pallas (Mosaic) calls? GSPMD cannot partition those,
    # so the dispatcher runs them under ``shard_map`` when a mesh is
    # active (``core.api._on_rows``).
    mosaic = False


# ---------------------------------------------------------------- kernels
def _hadacore_kernel(x_ref, mats_ref, o_ref, *, n: int, compute_dtype):
    """One grid step: transform a (block_m, n) row block entirely in VMEM.

    The row block is cast to the plan's compute dtype (a no-op for bf16
    inputs on the default native rule -- no f32 VMEM copy); the matmul
    passes accumulate f32 on the MXU (``_apply_passes``)."""
    x = x_ref[...].astype(compute_dtype)
    bm = x.shape[0]
    mats = unpack_pass_mats(mats_ref, n)
    y = _apply_passes(x.reshape(bm, n), n, mats)
    o_ref[...] = y.reshape(x_ref.shape).astype(o_ref.dtype)


def _quantize_rows(y: jnp.ndarray, mode: str, axis=-1):
    """THE symmetric-absmax epilogue math: (q on the mode's grid, f32
    scales). Single source of truth -- the fused kernels, the XLA
    epilogue fallback (``core.api``), and the oracle (``ref_fused``) all
    call this so their numerics agree bit-for-bit.

    ``q`` is returned pre-cast (f32 values on the integer grid for int8;
    unconverted quotients for fp8) so callers control the final cast --
    the fused kernel casts at the VMEM->HBM store, the dequant variant
    round-trips through the storage dtype first. ``axis=None`` gives one
    per-tensor scale (never fusable: needs a global reduction).
    """
    qmax, _, is_int = QSPECS[mode]
    # multiply by the f32 reciprocal of the grid maximum rather than
    # divide: XLA turns a division by a constant into this multiply in
    # some programs and not in others, which left the scales of two
    # programs one ulp apart; written out, every program rounds alike
    s = (jnp.maximum(jnp.max(jnp.abs(y), axis=axis, keepdims=True), 1e-8)
         * np.float32(1.0 / qmax))
    q = y / s
    if is_int:
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q, s


def _dequantize(q: jnp.ndarray, s: jnp.ndarray, mode: str) -> jnp.ndarray:
    """Map ``_quantize_rows`` output back to real values through the
    storage grid (fp8 round-trips through the real dtype so mantissa
    truncation is reproduced exactly). f32 in, f32 out -- the other half
    of the single-source-of-truth epilogue math."""
    _, qdt, is_int = QSPECS[mode]
    if not is_int:
        q = q.astype(qdt).astype(jnp.float32)
    return q * s


def _fused_kernel(x_ref, mats_ref, q_ref, s_ref, *, n: int, mode: str,
                  compute_dtype):
    """Rotate a row block and quantize it before write-back: the quantized
    tensor plus scales are the only HBM outputs (paper's future-work
    fusion, generalized from int8 to fp8_e4m3 / fp8_e5m2). Passes run in
    the plan's compute dtype; the epilogue statistics stay f32."""
    x = x_ref[...].astype(compute_dtype)
    bm = x.shape[0]
    mats = unpack_pass_mats(mats_ref, n)
    y = _apply_passes(x.reshape(bm, n), n, mats)
    q, s = _quantize_rows(y.astype(jnp.float32), mode)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = s


def _fused_dequant_kernel(x_ref, mats_ref, o_ref, *, n: int, mode: str,
                          compute_dtype):
    """Rotate + quantize-dequantize (fake quant) in one VMEM-resident pass:
    the training-path twin of ``_fused_kernel``. Reproduces
    ``core.quant.quantize`` numerics exactly, including the fp8 cast
    round-trip through the real storage dtype."""
    x = x_ref[...].astype(compute_dtype)
    bm = x.shape[0]
    mats = unpack_pass_mats(mats_ref, n)
    y = _apply_passes(x.reshape(bm, n), n, mats)
    q, s = _quantize_rows(y.astype(jnp.float32), mode)
    o_ref[...] = _dequantize(q, s, mode).reshape(x_ref.shape).astype(o_ref.dtype)


def _rows(x: jnp.ndarray, n: int):
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return x.reshape(m, n), m


def _pad_rows(x2: jnp.ndarray, bm: int):
    pad = (-x2.shape[0]) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, pad


def _plan_mats(plan) -> jnp.ndarray:
    # (P, b, b) in the plan's compute dtype: the base matrices are the
    # multiply operands of every pass, so they ride the low-precision path
    # too (entries are +-scale; for pow-of-4 n the ortho scale is exact in
    # bf16, otherwise it rounds like any bf16 constant).
    return jnp.asarray(plan.mats, dtype=jnp.dtype(plan.compute_dtype))


# ----------------------------------------------------------------- pallas
def _pallas_rows_call(x, plan, interpret: bool, kernel, out_kinds,
                      in_place: bool = False):
    """Shared grid plumbing for every row-tiled kernel: flatten to rows,
    pad to the block_m tile, launch over the row grid, unpad, restore the
    leading shape. ``out_kinds`` is a sequence of ``("tile", dtype)``
    (a (block_m, n) output) or ``("rowscale", f32)`` (a (block_m, 1)
    per-row output, reshaped to ``(..., 1)``)."""
    n = plan.p
    mats = _plan_mats(plan)
    b = mats.shape[-1]
    orig_shape = x.shape
    x2, m = _rows(x, n)
    bm = plan.block_m or default_block_m(
        n, m, x.dtype, compute_dtype=jnp.dtype(plan.compute_dtype),
        epilogue=plan.epilogue)
    x2, pad = _pad_rows(x2, bm)
    mp = x2.shape[0]
    out_specs, out_shape = [], []
    for kind, dt in out_kinds:
        if kind == "tile":
            out_specs.append(pl.BlockSpec((bm, n), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((mp, n), dt))
        else:
            out_specs.append(pl.BlockSpec((bm, 1), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((mp, 1), dt))
    single = len(out_kinds) == 1
    res = pl.pallas_call(
        kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((mats.shape[0], b, b), lambda i: (0, 0, 0)),
        ],
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        input_output_aliases={0: 0} if in_place else {},
        interpret=interpret,
    )(x2, mats)
    outs = (res,) if single else tuple(res)
    if pad:
        outs = tuple(o[:m] for o in outs)
    outs = tuple(
        o.reshape(orig_shape) if kind == "tile"
        else o.reshape(orig_shape[:-1] + (1,))
        for o, (kind, _) in zip(outs, out_kinds)
    )
    return outs[0] if single else outs


@functools.partial(jax.jit, static_argnames=("plan", "interpret", "in_place"))
def _pallas_transform(x, plan, interpret: bool, in_place: bool = False):
    TRACE_COUNTS[("pallas", "transform")] += 1
    kernel = functools.partial(
        _hadacore_kernel, n=plan.p,
        compute_dtype=jnp.dtype(plan.compute_dtype))
    return _pallas_rows_call(x, plan, interpret, kernel,
                             [("tile", x.dtype)], in_place)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _pallas_fused(x, plan, interpret: bool):
    TRACE_COUNTS[("pallas", "fused")] += 1
    mode = plan.epilogue.mode
    kernel = functools.partial(
        _fused_kernel, n=plan.p, mode=mode,
        compute_dtype=jnp.dtype(plan.compute_dtype))
    return _pallas_rows_call(
        x, plan, interpret, kernel,
        [("tile", QSPECS[mode][1]), ("rowscale", jnp.float32)])


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _pallas_fused_dequant(x, plan, interpret: bool):
    TRACE_COUNTS[("pallas", "fused_dequant")] += 1
    kernel = functools.partial(
        _fused_dequant_kernel, n=plan.p, mode=plan.epilogue.mode,
        compute_dtype=jnp.dtype(plan.compute_dtype))
    return _pallas_rows_call(x, plan, interpret, kernel, [("tile", x.dtype)])


@register_backend
class PallasBackend(Backend):
    name = "pallas"
    priority = 20
    quant_dot_fused = True
    mosaic = True

    def supports(self, p: int) -> bool:
        return p <= MAX_KERNEL_SIZE

    def transform(self, x, plan, interpret, in_place: bool = False):
        return _pallas_transform(x, plan, interpret, in_place)

    def fused(self, x, plan, interpret):
        return _pallas_fused(x, plan, interpret)

    def fused_dequant(self, x, plan, interpret):
        return _pallas_fused_dequant(x, plan, interpret)

    def quant_dot(self, x, wq, sw, plan, interpret, schedule=None,
                  check=None):
        # lazy import: quant_dot.py imports this module at load time.
        # ``check`` (ABFT column checksum) switches to the verified
        # kernel variant and the return value becomes (out, resid).
        from repro.kernels.quant_dot import pallas_quant_dot

        return pallas_quant_dot(x, wq, sw, plan, interpret,
                                schedule=schedule, check=check)

    def quant_dot_experts(self, x, wq, sw, plan, interpret, schedule=None,
                          check=None):
        from repro.kernels.quant_dot import pallas_quant_dot_experts

        return pallas_quant_dot_experts(x, wq, sw, plan, interpret,
                                        schedule=schedule, check=check)


# -------------------------------------------------------------------- xla
@functools.partial(jax.jit, static_argnames=("plan",))
def _xla_transform(x, plan):
    TRACE_COUNTS[("xla", "transform")] += 1
    n = plan.p
    cd = jnp.dtype(plan.compute_dtype)
    mats = [jnp.asarray(m, dtype=cd)
            for m in unpack_pass_mats(plan.mats, n)]
    orig_shape, orig_dtype = x.shape, x.dtype
    x2, _ = _rows(x.astype(cd), n)
    y = _apply_passes(x2, n, mats)
    return y.reshape(orig_shape).astype(orig_dtype)


@register_backend
class XlaBackend(Backend):
    name = "xla"
    priority = 10

    def supports(self, p: int) -> bool:
        return True

    def transform(self, x, plan, interpret):
        return _xla_transform(x, plan)

    def quant_dot(self, x, wq, sw, plan, interpret, schedule=None):
        # unfused oracle semantics: factored rotate, shared epilogue+dot
        # math (pjit-shardable -- every op is a reshape/dot). Grid
        # schedules do not apply here (there is no kernel grid); the
        # name is still validated so typos fail loudly on every backend.
        from repro.kernels.quant_dot import _resolve_schedule

        _resolve_schedule(schedule)
        from repro.kernels.quant_dot import xla_quant_dot

        return xla_quant_dot(x, wq, sw, plan, interpret)


# -------------------------------------------------------------------- ref
@functools.partial(jax.jit, static_argnames=("plan",))
def _ref_transform(x, plan):
    TRACE_COUNTS[("ref", "transform")] += 1
    y = fwht(x.astype(jnp.float32), plan.scale)
    return y.astype(x.dtype)


@register_backend
class RefBackend(Backend):
    name = "ref"
    priority = 0
    auto = False  # oracle: explicit selection only

    def supports(self, p: int) -> bool:
        return True

    def transform(self, x, plan, interpret):
        return _ref_transform(x, plan)
