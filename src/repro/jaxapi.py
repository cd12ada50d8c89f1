"""The JAX interfaces whose names or defaults moved between releases,
and the decisions that depend on the platform, kept in one module so
that an upgrade or a new chip touches one file. Written for jax 0.9;
older releases are not supported.

Meshes are the exception: every mesh is built by
``repro.launch.mesh.make_mesh``, which pins the axis types.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = ["ANY", "ClosedJaxpr", "Jaxpr", "fp8_operand_dtype",
           "interpret_mode", "shard_map", "tpu_compiler_params"]

# Memory space of a pallas operand that the kernel moves itself (no
# BlockSpec slicing; the streamed schedule DMAs its tiles from it).
ANY = pl.ANY


def tpu_compiler_params(*dimension_semantics: str):
    """Mosaic compiler parameters for a grid with these per-axis
    semantics (``"parallel"`` / ``"arbitrary"``)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the
    callers' bodies run pallas kernels and collectives whose outputs the
    checker cannot type."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def interpret_mode(platform: Optional[str] = None) -> bool:
    """Whether Pallas kernels run in interpret mode on ``platform``
    (default: ``jax.default_backend()``). True on the CPU, where the
    interpreter runs the same kernel bodies; False on a TPU, where Mosaic
    compiles them. Any other platform raises: the kernels are written for
    the TPU, and nothing may quietly swap in the interpreter on a device
    it was not meant for."""
    platform = jax.default_backend() if platform is None else platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted); "
        f"platform {platform!r} is neither")


def fp8_operand_dtype(platform: Optional[str] = None):
    """The dtype an XLA (not Pallas) contraction of fp8 grid values runs
    in on ``platform`` (default: ``jax.default_backend()``). fp8 grids
    embed exactly in both: bf16 is the MXU's operand type on the TPU,
    and the CPU backend has no batched bf16 dot."""
    platform = jax.default_backend() if platform is None else platform
    return jnp.bfloat16 if platform == "tpu" else jnp.float32
