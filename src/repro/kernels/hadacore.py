"""HadaCore for TPU: MXU-accelerated Walsh-Hadamard transform Pallas kernel.

Paper mapping (DESIGN.md section 2):

  * GPU 16x16 Tensor Core mma base case  ->  128-point base case on the
    128x128 MXU (``jnp.dot`` with f32 accumulation inside the kernel).
  * warp-shuffle / shared-memory transposes between passes  ->  in-VMEM
    reshape/swapaxes (Mosaic lowers these to vreg/sublane moves; no HBM
    round-trip between passes -- the whole row block stays resident).
  * threadblock-per-row grid  ->  Pallas grid over row blocks with a
    ``(block_m, n)`` BlockSpec VMEM tile.
  * paper section 3.3 non-power-of-16 sizes  ->  the r-pass matrix is the
    block-diagonal tiling I_{128/r} (x) H_r, so every pass stays a
    128-wide MXU matmul.
  * Appendix B in-place rotation  ->  ``input_output_aliases={0: 0}``:
    the output buffer IS the input buffer, halving HBM footprint (the TPU
    analogue of halving the L2 working set).
  * Appendix C BF16  ->  MXU always accumulates f32; we down-convert at
    the very end (conversion cost amortized over all passes).

Like the paper's kernel (and the Dao-AILab kernel it beats), a single
kernel invocation supports transform sizes up to 2^15 = 32768; the wrapper
falls back to the pure-JAX factored path above that.

The kernel bodies and their grid/BlockSpec wrappers now live in
``repro.kernels.registry`` (the ``pallas`` backend of the plan-based API);
``hadacore`` remains the direct, rotation-only entry point for callers
that want the kernel specifically (benchmarks, kernel tests).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.api import plan_for
from repro.jaxapi import interpret_mode
from repro.kernels.ref import is_pow2
from repro.kernels.registry import (  # noqa: F401  (re-exported: legacy API)
    MAX_KERNEL_SIZE,
    _pallas_transform,
    default_block_m,
)

__all__ = ["hadacore", "MAX_KERNEL_SIZE", "default_block_m"]


def hadacore(
    x: jnp.ndarray,
    scale: Optional[str] = "ortho",
    *,
    block_m: Optional[int] = None,
    interpret: Optional[bool] = None,
    in_place: bool = False,
) -> jnp.ndarray:
    """HadaCore Walsh-Hadamard transform of the last axis (Pallas TPU kernel).

    Args:
      x: (..., n) with n a power of 2, n <= 32768 for the kernel path.
      scale: "ortho" (1/sqrt(n) rotation) or None (+-1 transform).
      block_m: rows per grid step (None = VMEM-budget heuristic).
      interpret: run the kernel body in interpret mode (None = auto:
        compiled on a TPU, interpreted on the CPU; see
        ``jaxapi.interpret_mode``).
      in_place: alias the output onto the input buffer (Appendix B).
    """
    n = x.shape[-1]
    if n > MAX_KERNEL_SIZE:
        raise ValueError(
            f"hadacore kernel supports n <= {MAX_KERNEL_SIZE} (paper cap); "
            f"got {n}. Use repro.core.hadamard.hadamard_transform."
        )
    if not is_pow2(n):
        raise ValueError(f"Hadamard size must be a power of 2, got {n}")
    if interpret is None:
        interpret = interpret_mode()
    plan = plan_for(
        n, dtype=x.dtype, scale=scale, backend="pallas", block_m=block_m
    )
    return _pallas_transform(x, plan, interpret, in_place)
