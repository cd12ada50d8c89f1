"""Tensorized Kronecker-factored Walsh-Hadamard transform (pure JAX).

This is the XLA-level embodiment of the paper's idea: instead of log2(n)
scalar butterfly stages, run dense matmul passes against Hadamard
matrices sized for the TPU MXU (DESIGN.md section 2).

The Pallas kernels in ``repro.kernels`` run the same pass function
(``_apply_passes``) on VMEM tiles; this module is the portable path used
inside models (it shards trivially under pjit because every op is a
reshape/transpose/dot) and the reference for the kernels' pass math.

Factorization: with b = min(n, 128) and a = n / b,

    H_n = H_a (x) H_b                            (Kronecker, b minor)

so a row, viewed as an (a, b) matrix X, transforms as H_a X H_b: one
matmul over the 128 contiguous lanes, one over the a rows.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import hadamard_matrix, is_pow2

__all__ = [
    "MXU_TILE",
    "COMPUTE_DTYPES",
    "base_matrices",
    "base_matrices_np",
    "pack_pass_mats",
    "unpack_pass_mats",
    "hadamard_transform",
    "grouped_hadamard",
    "largest_pow2_divisor",
    "resolve_scale",
    "resolve_compute_dtype",
    "hadamard_check",
]

MXU_TILE = 128

# Dtypes the transform passes may run in. The MXU multiplies 16-bit
# operands at full rate and always accumulates f32 (preferred_element_type)
# -- the paper's Appendix C recipe, and the Markidis/Ootomo low-precision-
# multiply + f32-accumulate setup.
COMPUTE_DTYPES = ("float32", "bfloat16", "float16")


def resolve_compute_dtype(input_dtype, requested=None) -> str:
    """Resolve the dtype the matmul passes run in (canonical name).

    ``requested=None`` picks the native rule: 16-bit inputs (bf16/fp16)
    run the passes in their own dtype -- no f32 VMEM copy, half the
    compute-tile footprint, full-rate MXU multiplies with f32
    accumulation -- while everything else computes in f32. An explicit
    request (one of ``COMPUTE_DTYPES``) overrides the rule, e.g. to force
    f32 passes on bf16 data for an accuracy A/B.
    """
    if requested is not None:
        name = jnp.dtype(requested).name
        if name not in COMPUTE_DTYPES:
            raise ValueError(
                f"unsupported compute dtype {requested!r}; expected one of "
                f"{COMPUTE_DTYPES}"
            )
        return name
    name = jnp.dtype(input_dtype).name
    return name if name in ("bfloat16", "float16") else "float32"


def resolve_scale(scale, n: int) -> Optional[float]:
    """Resolve a user-facing ``scale`` argument to a numeric multiplier.

    Accepted values: ``"ortho"`` (1/sqrt(n), the orthonormal rotation),
    ``None`` (the unnormalized +-1 transform), or an explicit number.
    Anything else -- e.g. the typo ``"orth"`` that used to silently fall
    through to the unscaled transform -- raises ``ValueError``.
    """
    if scale is None:
        return None
    if isinstance(scale, str):
        if scale == "ortho":
            return 1.0 / math.sqrt(n)
        raise ValueError(
            f"unknown Hadamard scale {scale!r}: expected 'ortho', None, "
            "or an explicit numeric scale"
        )
    if isinstance(scale, (int, float)) and not isinstance(scale, bool):
        return float(scale)
    raise ValueError(f"unknown Hadamard scale {scale!r}")


def base_matrices_np(n: int, scale: Optional[float]) -> List[np.ndarray]:
    """The transform's matrices (numpy f32), lane factor FIRST.

    Sylvester Hadamards compose by Kronecker product, so with
    ``b = min(n, 128)`` and ``a = n / b``, ``H_n = H_a (x) H_b``: viewing
    a row as an (a, b) matrix X (row-major), the transform is
    ``H_a X H_b``. Returns ``[H_b]`` for n <= 128 and ``[H_b, H_a]``
    otherwise (a <= 256 under the kernel cap). ``scale`` is folded into
    the first matrix -- a free normalization, one of the
    micro-optimizations the scalar algorithm pays a full extra pass (or
    per-stage multiply) for.
    """
    if not is_pow2(n):
        raise ValueError(f"Hadamard size must be a power of 2, got {n}")
    b = min(n, MXU_TILE)
    mats = [hadamard_matrix(b)]
    if n > b:
        mats.append(hadamard_matrix(n // b))
    if scale is not None:
        mats[0] = mats[0] * np.float32(scale)
    return mats


def pack_pass_mats(mats: List[np.ndarray]) -> np.ndarray:
    """Stack the pass matrices into one (P, c, c) array, zero-padded to
    the largest -- the single matrix operand every kernel takes."""
    c = max(m.shape[0] for m in mats)
    out = np.zeros((len(mats), c, c), np.float32)
    for i, m in enumerate(mats):
        out[i, :m.shape[0], :m.shape[1]] = m
    return out


def unpack_pass_mats(packed, n: int) -> list:
    """Inverse of ``pack_pass_mats`` for an n-point plan; works on numpy
    arrays, jax arrays and Pallas refs alike (static slices only)."""
    b = min(n, MXU_TILE)
    mats = [packed[0, :b, :b]]
    if n > b:
        mats.append(packed[1, :n // b, :n // b])
    return mats


def base_matrices(n: int, scale: Optional[float], dtype=jnp.float32) -> List[jnp.ndarray]:
    """``base_matrices_np`` as device arrays (see DESIGN.md section 2)."""
    return [jnp.asarray(m, dtype=dtype) for m in base_matrices_np(n, scale)]


def _apply_passes(x: jnp.ndarray, n: int, mats: List[jnp.ndarray]) -> jnp.ndarray:
    """Shared pass structure ``H_a X H_b`` (``base_matrices_np``): one
    matmul over the 128 lanes of each (a, 128) row tile, then one over
    its a sublane rows, which a minor-axis transpose brings to the lanes.
    Every operand keeps 128 lanes or the full tile, the layouts Mosaic
    lowers. ``x`` has shape (M, n) and is already in the COMPUTE dtype
    (f32, bf16 or fp16); every matmul accumulates in f32 on the MXU
    (``preferred_element_type``), the transposes move f32 values, and
    the result of each pass is rounded to the compute dtype. Runs
    unchanged inside the Pallas kernel body and under plain jit."""
    m = x.shape[0]
    cd = x.dtype
    mats = [mt if mt.dtype == cd else mt.astype(cd) for mt in mats]

    def mm(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    if len(mats) == 1:
        return mm(x, mats[0]).astype(cd)
    a = n // MXU_TILE
    y = mm(x.reshape(m * a, MXU_TILE), mats[0]).astype(cd)
    y = jnp.swapaxes(y.astype(jnp.float32).reshape(m, a, MXU_TILE), 1, 2)
    y = mm(y.reshape(m * MXU_TILE, a).astype(cd), mats[1])
    y = jnp.swapaxes(y.reshape(m, MXU_TILE, a), 1, 2)
    return y.reshape(m, n).astype(cd)


@partial(jax.jit, static_argnames=("scale",))
def _hadamard_transform_jit(x: jnp.ndarray, scale: Optional[float]) -> jnp.ndarray:
    n = x.shape[-1]
    mats = base_matrices(n, scale)
    orig_shape, orig_dtype = x.shape, x.dtype
    y = _apply_passes(x.astype(jnp.float32).reshape(-1, n), n, mats)
    return y.reshape(orig_shape).astype(orig_dtype)


def hadamard_transform(x: jnp.ndarray, scale: Optional[str] = "ortho") -> jnp.ndarray:
    """Right Hadamard transform of the last axis, MXU-factored, pure JAX.

    scale: "ortho" (1/sqrt(n), a rotation), None (+-1 transform), or an
    explicit numeric multiplier. Unknown strings raise ``ValueError``.
    """
    return _hadamard_transform_jit(x, resolve_scale(scale, max(x.shape[-1], 1)))


def hadamard_check(x: jnp.ndarray, y: jnp.ndarray, *, scale="ortho",
                   compute_dtype=None) -> jnp.ndarray:
    """Linearity invariant of a pure-rotation site (ABFT, DESIGN.md s14).

    The transform is linear, so the column-sum of the outputs must equal
    the transform of the column-sum of the inputs:

        sum_i H(x)[i, :]  ==  H(sum_i x[i, :])

    The reference side is recomputed here in f32 on the summed row -- a
    single (1, n) transform regardless of batch size, so the check costs
    ~1/m of the site it guards and adds no extra pallas_call. A corrupted
    output element (bit flip, clobbered tile) shifts one column sum by
    the corruption magnitude while the reference side is untouched.

    Tolerance has two terms, each scaled by the per-column absolute
    output mass: the compute/storage dtype's per-element rounding, whose
    errors over the m summed rows partially cancel (~colmass/sqrt(m),
    the dominant term at bf16/fp16), and the f32 summation/transform
    chains on both sides of the comparison (~eps_f32 * sqrt(m + n) *
    colmass, the dominant term at f32). C = 8 is calibrated with ~10x
    headroom over the measured healthy worst case across dtypes and
    shapes (tests/test_abft.py); detection sensitivity at bf16 is a
    fraction of a typical element, at f32 ~1e-5 relative. Returns a
    scalar bool verdict (True = site verified); non-finite outputs also
    fail (NaN compares unordered).
    """
    n = x.shape[-1]
    xr = x.reshape(-1, n).astype(jnp.float32)
    yr = y.reshape(-1, n).astype(jnp.float32)
    m = max(xr.shape[0], 1)
    cd = resolve_compute_dtype(x.dtype, compute_dtype)
    eps = float(jnp.finfo(jnp.dtype(cd)).eps)
    if jnp.issubdtype(jnp.dtype(y.dtype), jnp.floating):
        eps = max(eps, float(jnp.finfo(jnp.dtype(y.dtype)).eps))
    eps32 = float(jnp.finfo(jnp.float32).eps)
    ref = _apply_passes(jnp.sum(xr, axis=0, keepdims=True), n,
                        base_matrices(n, resolve_scale(scale, n)))
    got = jnp.sum(yr, axis=0, keepdims=True)
    colmass = jnp.sum(jnp.abs(yr), axis=0, keepdims=True)
    tol = 8.0 * (eps * (colmass / math.sqrt(m) + jnp.max(jnp.abs(yr)))
                 + eps32 * math.sqrt(m + n) * colmass) + 1e-30
    return jnp.all(jnp.abs(got - ref) <= tol)


def largest_pow2_divisor(n: int) -> int:
    return n & (-n)


def grouped_hadamard(x: jnp.ndarray, group: Optional[int] = None,
                     scale: Optional[str] = "ortho") -> jnp.ndarray:
    """Hadamard on contiguous groups of the last axis: y = x (I_g (x) H_p).

    This is how rotation-quantization handles non-power-of-2 contraction
    dims (d_ff = 14336 = 7 * 2048, 53248 = 13 * 4096, ...) and
    tensor-parallel shards: the transform stays exact, orthogonal and
    collective-free (DESIGN.md section 3). ``group`` defaults to the
    largest power-of-2 divisor of the axis size.
    """
    n = x.shape[-1]
    p = group if group is not None else largest_pow2_divisor(n)
    if n % p != 0 or not is_pow2(p):
        raise ValueError(f"group {p} must be a power-of-2 divisor of {n}")
    if p == 1:
        return x
    xg = x.reshape(*x.shape[:-1], n // p, p)
    yg = hadamard_transform(xg, scale=scale)
    return yg.reshape(x.shape)
