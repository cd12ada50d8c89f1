"""Mutation-test fixture: intentionally broken kernel variants the
linter MUST flag (proof the rules have teeth, wired into CI's lint
job via ``python -m repro.analysis.lint --mutation``).

Two committed mutants, one per headline rule family:

* :func:`_mutant_unguarded_rotate` drops the ``j == 0`` guard from the
  rotate-once kernel -- every out-channel revisit re-transforms the row
  block, the exact regression PR 5 eliminated. The
  ``rotate-once-contract`` rule must fire.
* :func:`_mutant_dangling_dma` issues the ring's copy-starts
  UNGUARDED at top level and never waits on the semaphores -- the
  contraction races the DMA and a copy is in flight when the j loop
  ends. The ``dma-safety`` rule must fire (unmatched + unguarded).

The mutants only need to TRACE (``jax.make_jaxpr`` runs abstract
evaluation, never the kernel), so the broken bodies are never
executed.
"""
from __future__ import annotations

import functools
from typing import List

from repro.analysis.sites import Site, traced

__all__ = ["mutant_sites"]


def _mutant_unguarded_rotate(x_ref, mats_ref, wq_ref, sw_ref, o_ref,
                             q_ref, s_ref, *, n, mode, compute_dtype):
    """BROKEN rotate-once body: the rotate+quantize stage runs on EVERY
    grid step (no ``pl.when(j == 0)``), so the transform matmuls sit at
    top level instead of under the cond."""
    from repro.kernels.quant_dot import (_operand_dot, _operand_from_q,
                                         _rotate_quantize_block)

    q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                  compute_dtype=compute_dtype)
    q_ref[...] = _operand_from_q(q, mode)
    s_ref[...] = s
    acc = _operand_dot(q_ref[...], wq_ref[...], mode)
    o_ref[...] = (acc * s_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _mutant_dangling_dma(x_ref, mats_ref, wq_hbm, sw_hbm, o_ref,
                         q_ref, s_ref, w_ring, sw_ring, w_sem, s_sem,
                         *, n, mode, compute_dtype, bn, nj):
    """BROKEN streamed body: the weight/scale copy-starts are issued
    unconditionally (no warm-up/prefetch guards) and NEVER waited on --
    the contraction reads the ring slot while the DMA is still in
    flight, and a start dangles at the end of every row block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.quant_dot import (_operand_dot, _operand_from_q,
                                         _rotate_quantize_block)

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        q_ref[...] = _operand_from_q(q, mode)
        s_ref[...] = s

    pltpu.make_async_copy(wq_hbm.at[:, pl.ds(j * bn, bn)], w_ring.at[0],
                          w_sem.at[0]).start()
    pltpu.make_async_copy(sw_hbm.at[:, pl.ds(j * bn, bn)], sw_ring.at[0],
                          s_sem.at[0]).start()
    acc = _operand_dot(q_ref[...], w_ring[0], mode)
    o_ref[...] = (acc * s_ref[...] * sw_ring[0]).astype(o_ref.dtype)


def _launch(kernel, schedule: str, *, n=256, d=640, m=8, bn=128):
    """pallas_call plumbing identical to ``_pallas_quant_dot``'s for the
    given schedule, with the broken body swapped in."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from repro.core.api import QuantEpilogue, plan_for
    from repro.jaxapi import ANY
    from repro.kernels.quant_dot import _scratch_dtype, quant_dot_blocks
    from repro.kernels.registry import _plan_mats

    plan = plan_for(n, backend="pallas", epilogue=QuantEpilogue("int8"))
    mats = _plan_mats(plan)
    dec = quant_dot_blocks(n, d, m, jnp.float32, plan.compute_dtype,
                           "int8", block_n=bn, schedule=schedule)
    bm = dec.block_m
    mp = -(-m // bm) * bm
    common = dict(n=n, mode="int8", compute_dtype=jnp.dtype(
        plan.compute_dtype))
    wq_spec = pl.BlockSpec((n, bn), lambda i, j: (0, j))
    sw_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    scratch = [pltpu.VMEM((bm, n), _scratch_dtype("int8")),
               pltpu.VMEM((bm, 1), jnp.float32)]
    if schedule == "streamed":
        body = functools.partial(kernel, **common, bn=bn, nj=d // bn)
        scratch += [pltpu.VMEM((2, n, bn), jnp.int8),
                    pltpu.VMEM((2, 1, bn), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,))]
        wq_spec = pl.BlockSpec(memory_space=ANY)
        sw_spec = pl.BlockSpec(memory_space=ANY)
    else:
        body = functools.partial(kernel, **common)

    def call(x, wq, sw):
        return pl.pallas_call(
            body,
            grid=(mp // bm, d // bn),
            in_specs=[
                pl.BlockSpec((bm, n), lambda i, j: (i, 0)),
                pl.BlockSpec((mats.shape[0],) + mats.shape[1:],
                             lambda i, j: (0, 0, 0)),
                wq_spec,
                sw_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, d), jnp.float32),
            scratch_shapes=scratch,
            interpret=True,
        )(x, mats, wq, sw)

    x = jnp.zeros((mp, n), jnp.float32)
    wq = jnp.zeros((n, d), jnp.int8)
    sw = jnp.ones((1, d), jnp.float32)
    jaxpr, qw, shim = traced(call, x, wq, sw)
    return jaxpr, plan, dec, qw, shim


def mutant_sites() -> List[Site]:
    """The committed mutants as lint sites; a healthy linter reports
    violations on BOTH (CI runs ``lint --mutation`` and requires a
    nonzero exit)."""
    jaxpr, plan, dec, qw, shim = _launch(_mutant_unguarded_rotate,
                                         "rotate_once")
    broken_rotate = Site(
        name="mutant[unguarded_rotate]", kind="kernel", jaxpr=jaxpr,
        schedule="rotate_once", plan=plan, decision=dec,
        qw_calls=qw, shim_calls=shim)
    jaxpr, plan, dec, qw, shim = _launch(_mutant_dangling_dma, "streamed")
    broken_dma = Site(
        name="mutant[dangling_dma]", kind="kernel", jaxpr=jaxpr,
        schedule="streamed", plan=plan, decision=dec,
        qw_calls=qw, shim_calls=shim)
    return [broken_rotate, broken_dma]
