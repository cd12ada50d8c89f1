"""Fused rotate -> quantize -> GEMM consumer kernel (the quantized hot
path, end to end in low precision) with a ROTATE-ONCE grid schedule.

The paper's kernel makes the online rotation cheap; its *consumer* is a
quantized matmul (QuaRot down-proj, FP8 attention). PR 1 fused the
rotation with the quantize epilogue so the quantized tensor is the only
HBM output -- but the consumer GEMM still read it back from HBM and the
models fake-quantized both operands in f32. PR 3 closed that loop with a
2D (row blocks x out-channel blocks) grid whose every step rotated the
row block, quantized it, and contracted it against one weight tile:

  * int8 operands with int32 MXU accumulation (``preferred_element_type``)
  * fp8 operands multiplied exactly in bf16 (both fp8 grids embed exactly:
    <= 5 mantissa bits and products of two fp8 values fit bf16's 8) with
    f32 accumulation

applying ``scale_x * scale_w`` in the epilogue. The rotated/quantized
activations never round-trip through HBM.

PR 3's schedule, however, recomputed the rotate+quantize of each
(block_m, n) row block for EVERY out-channel tile j -- multiplying the
transform work by d/block_n (~8x at n=4096, d=4*4096) when the paper's
roofline argues the transform should cost ~k*128 flops/element ONCE per
row. The default schedule here is **rotate-once**:

  * the out-channel axis j is the INNERMOST grid axis and is declared
    sequential (``dimension_semantics=("parallel", "arbitrary")``): for a
    fixed row block i, the kernel visits j = 0, 1, ..., d/bn - 1 in order;
  * at j == 0 the row block is rotated in the plan's compute dtype
    (bf16/fp16 multiplies, f32 MXU accumulation -- the Markidis / Ootomo
    recipe), per-token quantized, and the DOT-OPERAND form of (q, s) is
    stashed in VMEM ``scratch_shapes`` (int8 for the int path, the exact
    bf16 embedding for fp8 -- so the scratch is also the cheapest legal
    operand representation);
  * every j (including 0) contracts the scratch operand against its
    (n, block_n) weight tile. The scratch outlives the j loop of its row
    block by construction (scratch persists across grid steps; j is
    sequential within each i), so each row is transformed exactly once
    regardless of d.

The PR-3 ``revisit`` schedule is kept selectable (``schedule="revisit"``
or ``REPRO_QUANT_DOT_SCHEDULE=revisit``) as the A/B baseline for the
transform-amortization benchmark; both schedules are bitwise identical
for int8 (the rotation/quantize/contraction math is unchanged -- only
*when* the transform runs differs).

**Streamed weight DMA** (``schedule="streamed"``): rotate-once made the
out-channel axis j sequential, which also made every weight-tile fetch
SYNCHRONOUS -- the implicit BlockSpec pipeline stalls the MXU between
bursts waiting on the (n, bn) tile of step j. The streamed schedule
keeps the rotate-once structure but takes over the weight movement with
a manual two-slot VMEM ring: the weight and scale operands are passed as
HBM/ANY-memory-space refs (no BlockSpec slicing), and at grid step j the
kernel

  * j == 0: starts the async copy of tile 0 into slot 0 (the ring
    warm-up -- the copy flies while the rotation+quantize below it runs,
    so even the first tile's latency hides behind the transform), then
    rotates/quantizes into the scratch exactly as rotate-once does;
  * every j < nj-1: starts the async copy of tile j+1 into slot
    ``(j+1) % 2`` BEFORE contracting tile j -- the DMA of the next tile
    overlaps the current MXU burst;
  * waits on slot ``j % 2``'s semaphore pair (one DMA semaphore per ring
    slot, weight and scale copies tracked separately), then contracts
    from that slot.

Slot parity resets at each new (expert, row block) pair for free: the
slot index is ``j % 2`` of the RESTARTED j loop and the j == 0 warm-up
re-primes slot 0, while the ``j + 1 < nj`` guard drains all in-flight
copies before the row block ends -- no DMA crosses a row-block (or
expert) boundary. ``quant_dot_blocks`` charges the second weight-tile
slot and the scale ring when sizing streamed blocks, so streamed block
sizes never oversubscribe VMEM.

Interpret mode has no real DMA engine (the XLA interpreter simulates
``make_async_copy`` synchronously), so off-TPU dispatch of
``schedule="streamed"`` degrades to ``rotate_once`` -- warned once per
process and counted in ``TRACE_COUNTS[("quant_dot", "stream_fallback")]``
(mirroring the sharded-dispatch ``_sharded_fallback`` observability).
Setting ``REPRO_QUANT_DOT_STREAM_INTERPRET=1`` overrides the fallback
and runs the real streamed body under the interpreter: the simulated
copies are synchronous (no overlap win) but bit-exact, which is how the
schedule-parity tests and the bench A/B exercise the streamed kernels
off-TPU.

``pallas_quant_dot_experts`` extends the same schedule to the stacked
MoE expert weights on a 3-D (expert, row blocks, out-channel blocks)
grid, so the expert consumer stops splitting into a rotate+quantize
kernel plus a per-expert XLA einsum.

``epilogue_dot`` is the single source of truth for the quantized-GEMM
math; the unfused fallback (grouped transforms, per-tensor scales,
``xla_quant_dot`` -- the pjit-shardable path and the test oracle) shares
it so fused and unfused paths agree bit-for-bit in the contraction.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hadamard import _apply_passes, unpack_pass_mats
from repro.jaxapi import ANY, tpu_compiler_params
from repro.kernels.registry import (
    QSPECS,
    TRACE_COUNTS,
    _VMEM_BUDGET_BYTES,
    _pad_rows,
    _plan_mats,
    _quantize_rows,
    _rows,
    _xla_transform,
    warn_once,
)

__all__ = ["pallas_quant_dot", "pallas_quant_dot_experts", "xla_quant_dot",
           "xla_quant_dot_resid", "epilogue_dot", "quant_dot_blocks",
           "BlockDecision", "SCHEDULE_ENV_VAR", "SCHEDULES",
           "STREAM_INTERPRET_ENV"]

_CONTRACT = (((1,), (0,)), ((), ()))  # plain (m, k) @ (k, n)

# Largest contraction dim whose worst-case int8 x int8 row sum stays in
# int32: 127 * 127 * 2^17 ~= 2.11e9 < 2^31 - 1 (2^18 would wrap). Only
# the above-cap XLA fallback can exceed this -- the kernel caps at 2^15.
_INT32_SAFE_K = 1 << 17

# fp8 weight-tile bytes/element inside the kernel: the 1-byte storage
# grid, the f32 value its conversion to bf16 goes through on a chip
# without fp8 arithmetic (v5e), and the exact bf16 embedding the dot
# runs in. Charged at 3 (no f32), an n=2048 tile of 1024 out-channels
# overflowed the 16 MiB scoped VMEM at any row block.
_FP8_OPERAND_BYTES = 7

# f32 bytes/element of the row block that the j == 0 rotate+quantize
# holds at once: the pass output, its minor-axis transpose and the
# quantizer's quotient. Mosaic allocates them for the whole kernel, so
# leaving them out let fp8 prefill tiles past the compiler's 16 MiB
# scoped-VMEM limit on v5e.
_ROTATE_TEMP_BYTES = 12

# Below this n the a = n/128 sublane rows of a row tile are too few to
# fill the lanes of the transposed (128, a) temporary, which Mosaic then
# pads: a row's temporaries cost what they cost at this n, not less (on
# v5e the n=512 transform compiles at 256 rows a block and not at 512).
_ROTATE_TEMP_MIN_N = 4096

SCHEDULE_ENV_VAR = "REPRO_QUANT_DOT_SCHEDULE"
SCHEDULES = ("rotate_once", "revisit", "streamed")

# Set to a truthy value ("1"/"true"/"force") to run the REAL streamed
# kernel body under interpret mode instead of the rotate_once fallback:
# the interpreter simulates each async copy synchronously (no overlap
# win, bit-exact results) -- the hook the schedule-parity tests and the
# bench A/B use to exercise the DMA ring off-TPU.
STREAM_INTERPRET_ENV = "REPRO_QUANT_DOT_STREAM_INTERPRET"

# The streamed->rotate_once interpret fallback warns once per process via
# the shared ``registry.warn_once`` idiom;
# TRACE_COUNTS[("quant_dot", "stream_fallback")] keeps counting every
# dispatch (tests reset the warning via WARN_ONCE_SEEN, never the counter).


def _operand_from_q(q, mode):
    """Cast ``_quantize_rows`` output to the grid the contraction runs on:
    int8 for the int path (int32 MXU accumulation), the exact bf16
    embedding of the fp8 grid otherwise. This is the representation the
    rotate-once schedule stashes in VMEM scratch -- 1 (int8) or 2 (bf16)
    bytes/element, and directly consumable by every subsequent weight
    tile."""
    if QSPECS[mode][2]:
        return q.astype(jnp.int8)
    return q.astype(QSPECS[mode][1]).astype(jnp.bfloat16)


def _operand_dot(a, wq, mode):
    """Contract a dot-operand activation block (``_operand_from_q`` form)
    against the storage-dtype weight tile. Returns f32."""
    if QSPECS[mode][2]:
        acc = jax.lax.dot_general(a, wq.astype(jnp.int8), _CONTRACT,
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32)
    return jax.lax.dot_general(a, wq.astype(jnp.bfloat16), _CONTRACT,
                               preferred_element_type=jnp.float32)


def _low_precision_dot(q, wq, mode):
    """The quantized contraction on the mode's native arithmetic: int8
    operands accumulate exactly in int32; fp8 operands are embedded in
    bf16 (exact) and accumulate f32. ``q`` comes from ``_quantize_rows``
    pre-cast (f32 values on the grid). Returns f32."""
    is_int = QSPECS[mode][2]
    if is_int and q.shape[-1] > _INT32_SAFE_K:
        # contraction too long for exact int32: f32 accumulation of the
        # exact grid products (values <= 127 are f32-exact)
        return jax.lax.dot_general(
            q, wq.astype(jnp.float32), _CONTRACT,
            preferred_element_type=jnp.float32)
    return _operand_dot(_operand_from_q(q, mode), wq, mode)


def epilogue_dot(q, s, wq, sw, mode: str, out_dtype) -> jnp.ndarray:
    """``(q * s) @ (wq * sw)`` with the scales factored OUT of the matmul:
    ``(q @ wq) * s * sw`` -- exact because s is per row of q and sw per
    column of wq. q: (..., n) grid values, s broadcastable per-token (or
    per-tensor) scales, wq: (n, d) storage-dtype weight, sw: (1, d)."""
    lead = q.shape[:-1]
    n, d = q.shape[-1], wq.shape[-1]
    acc = _low_precision_dot(q.reshape(-1, n), wq, mode).reshape(*lead, d)
    return (acc * s * sw.reshape((1,) * len(lead) + (d,))).astype(out_dtype)


def _operand_bytes(mode: str) -> int:
    """Bytes/element of the scratch-resident dot operand (int8 grid or
    bf16 fp8-embedding)."""
    return 1 if QSPECS[mode][2] else 2


class BlockDecision(tuple):
    """The ``(block_m, block_n)`` tile decision, as a tuple subclass so
    every historical ``bm, bn = quant_dot_blocks(...)`` unpack (and
    ``== (bm, bn)`` comparison) keeps working, carrying the metadata the
    benches log alongside the tiles:

    * ``schedule``   -- the grid schedule the sizes were charged for
      (the streamed DMA ring costs a second weight-tile slot + a scale
      ring, so its block sizes can be narrower);
    * ``vmem_bytes`` -- the estimated VMEM high-water mark of the chosen
      tiles under that schedule: within the kernel budget, except where
      the smallest tile (one sublane group of rows x 128 out-channels)
      exceeds it and is returned anyway -- fp8 at n=8192 is charged
      9.7 MiB, which Mosaic compiles within its 16 MiB limit.
    """

    schedule: str
    vmem_bytes: int

    def __new__(cls, block_m: int, block_n: int, schedule: str,
                vmem_bytes: int):
        self = super().__new__(cls, (block_m, block_n))
        self.schedule = schedule
        self.vmem_bytes = vmem_bytes
        return self

    @property
    def block_m(self) -> int:
        return self[0]

    @property
    def block_n(self) -> int:
        return self[1]

    def __repr__(self):
        return (f"BlockDecision(block_m={self[0]}, block_n={self[1]}, "
                f"schedule={self.schedule!r}, vmem_bytes={self.vmem_bytes})")


def quant_dot_blocks(n: int, d: int, m: int, dtype, compute_dtype,
                     mode: str, block_m=None, block_n=None,
                     schedule: str = "rotate_once",
                     abft: bool = False) -> BlockDecision:
    """The tile decision for the fused kernel, charging every VMEM
    resident of the requested schedule: the input tile + compute-dtype
    working copy per row, the SCRATCH dot-operand tile (int8 / bf16) + the
    per-row f32 scale that live across the j loop, the weight tile(s),
    the (block_m, block_n) output tile, and the per-out-channel scales.

    ``schedule="streamed"`` charges the DMA ring on top: a SECOND
    (n, block_n) weight-tile slot in the storage dtype plus the two-slot
    f32 scale ring (the DMA semaphores are register-file residents --
    free as far as this budget is concerned), so streamed block sizes
    never oversubscribe VMEM. The chosen schedule and the estimated VMEM
    high-water mark ride along on the returned :class:`BlockDecision`
    (a (block_m, block_n) tuple) so benches can record the decision.

    A user-pinned ``block_m`` (``plan.block_m``) is honored BEFORE any
    sizing decision, so the weight-tile / ``block_n`` tradeoff is
    computed against the row count that will actually run -- not against
    a heuristic ``bm`` that the pin then overrides. ``block_n`` pins the
    out-channel tile the same way (benchmarks use it to hold the revisit
    count fixed across schedules).

    Because the rotate-once schedule makes weight-tile revisits free of
    transform recompute, ``block_n`` is allowed up to 1024 (PR 3 capped
    it at 512 to keep the per-revisit transform bill bounded).

    ``abft=True`` charges the checksum-verified kernel variant: the
    (1, n) f32 column-checksum input tile (block-constant across the
    grid) plus 12 bytes/row for the per-row verification residents (the
    f32 chk + acc scratch columns and the residual output tile). Block
    sizes may therefore differ from the unverified decision -- harmless,
    because every output element is computed from its full n-contraction
    regardless of tiling (the schedule-parity tests assert bitwise
    identity across decisions)."""
    in_b = jnp.dtype(dtype).itemsize
    cb = jnp.dtype(compute_dtype).itemsize
    is_int = QSPECS[mode][2]
    qb = _operand_bytes(mode)       # scratch operand bytes/element
    wb = 1 if is_int else _FP8_OPERAND_BYTES
    swb = 4                         # f32 per-out-channel scale tile
    if schedule == "streamed":
        # the ring's second weight slot holds the 1-byte STORAGE grid for
        # both paths (the fp8 bf16-embedding temporary is made per
        # contraction, never per slot), and the scale tile doubles
        wb += 1
        swb *= 2
    # per-row residents independent of bn: input tile + compute copy +
    # scratch operand + the rotation's f32 temporaries + f32 scratch scale
    row_fixed = (n * (in_b + cb + qb) + 4
                 + _ROTATE_TEMP_BYTES * max(n, _ROTATE_TEMP_MIN_N))
    fixed = 0
    if abft:
        row_fixed += 12             # chk + acc scratch + residual out tile
        fixed = n * 4               # (1, n) f32 column-checksum input

    def vmem(bm_, bn_):
        return fixed + bm_ * row_fixed + bn_ * (n * wb + bm_ * in_b + swb)

    # bn always steps in 128-lane multiples so the BlockSpec last dim
    # stays MXU-tiled
    bn = min(1024, -(-d // 128) * 128) if block_n is None else block_n
    if block_m is not None:
        if block_n is None:
            # pinned rows: the weight/output/sw tiles get everything the
            # rows leave
            avail = _VMEM_BUDGET_BYTES - fixed - block_m * row_fixed
            while bn > 128 and bn * (n * wb + block_m * in_b + swb) > avail:
                bn -= 128
        return BlockDecision(block_m, bn, schedule, vmem(block_m, bn))
    if block_n is None:
        # joint sizing: cap the weight tile at half the budget (oversizing
        # it starves block_m), then size the rows from the remainder
        while n * bn * wb > _VMEM_BUDGET_BYTES // 2 and bn > 128:
            bn -= 128
    per_row = row_fixed + bn * in_b
    bm = max(8, (_VMEM_BUDGET_BYTES - fixed - bn * (n * wb + swb)) // per_row)
    bm = min(bm, 256, m)
    sub = 16 if in_b == 2 else 8
    bm = max(sub, (bm // sub) * sub)
    return BlockDecision(bm, bn, schedule, vmem(bm, bn))


def _rotate_quantize_block(x, mats_ref, *, n: int, mode: str,
                           compute_dtype):
    """The shared transform+quantize stage: rotate a (block_m, n) row
    block in the compute dtype (f32 MXU accumulation) and per-token
    quantize. Returns ``(q, s)`` with q in ``_quantize_rows``'s pre-cast
    f32-grid form."""
    x = x.astype(compute_dtype)
    bm = x.shape[0]
    mats = unpack_pass_mats(mats_ref, n)
    y = _apply_passes(x.reshape(bm, n), n, mats)
    return _quantize_rows(y.astype(jnp.float32), mode)


def _quant_dot_kernel_rotate_once(x_ref, mats_ref, wq_ref, sw_ref, o_ref,
                                  q_ref, s_ref, *, n: int, mode: str,
                                  compute_dtype):
    """Rotate-once grid step. The out-channel axis j (innermost,
    sequential) revisits the same row block i with consecutive weight
    tiles; the rotation + per-token quantization run ONLY at j == 0 and
    their dot-operand form is stashed in VMEM scratch (``q_ref``: int8 or
    bf16 fp8-embedding, ``s_ref``: f32 per-row scales). Every j contracts
    the scratch operand against its (n, block_n) weight tile -- so each
    row is transformed exactly once regardless of d. Scratch persists
    across grid steps and j is sequential within each i, so the j == 0
    write is visible to every later j of that row block (and rows blocks
    may still run in parallel across cores: each partition owns its own
    scratch and walks its own j loop in order)."""

    @pl.when(pl.program_id(1) == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        q_ref[...] = _operand_from_q(q, mode)
        s_ref[...] = s

    acc = _operand_dot(q_ref[...], wq_ref[...], mode)
    o_ref[...] = (acc * s_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _ring_dmas(make_w, make_s, j, nj: int):
    """The two-slot DMA ring protocol shared by the streamed kernels.

    ``make_w(slot, jj)`` / ``make_s(slot, jj)`` build the async-copy
    descriptors for out-channel tile ``jj`` of the weight / scale operand
    into ring slot ``slot`` (each descriptor pairs a VMEM slot with its
    own DMA semaphore, weight and scale copies tracked separately).

    Calling this STARTS the j == 0 warm-up copy into slot 0 (so the
    caller's rotate+quantize below overlaps even the first tile's
    latency) and returns ``finish()``, which the caller invokes right
    before the contraction: it starts the prefetch of tile j+1 into the
    opposite slot (guarded by ``j + 1 < nj``, so no copy is ever in
    flight when the row block's j loop ends -- the slot parity of the
    next (expert, row block) pair resets cleanly to 0), waits on slot
    ``j % 2``'s semaphores, and returns that slot index."""
    slot = jax.lax.rem(j, 2)

    @pl.when(j == 0)
    def _warm_up():
        make_w(0, j).start()
        make_s(0, j).start()

    def finish():
        @pl.when(j + 1 < nj)
        def _prefetch_next():
            make_w(1 - slot, j + 1).start()
            make_s(1 - slot, j + 1).start()

        make_w(slot, j).wait()
        make_s(slot, j).wait()
        return slot

    return finish


def _quant_dot_kernel_streamed(x_ref, mats_ref, wq_hbm, sw_hbm, o_ref,
                               q_ref, s_ref, w_ring, sw_ring, w_sem, s_sem,
                               *, n: int, mode: str, compute_dtype,
                               bn: int, nj: int):
    """Streamed grid step: rotate-once structure + a manual two-slot VMEM
    ring over the weight/scale operands (``wq_hbm``/``sw_hbm`` are
    UNBLOCKED ANY-memory-space refs; the implicit BlockSpec weight
    pipeline is replaced by explicit ``make_async_copy``). Order per
    step j: start the warm-up copy (j == 0 only), rotate+quantize (j == 0
    only -- overlapping the warm-up copy), start the prefetch of tile
    j+1, wait on slot j % 2, contract from that slot. The DMA of tile
    j+1 is therefore in flight DURING the MXU burst of tile j -- the
    overlap rotate-once lost when it made j sequential."""
    j = pl.program_id(1)

    def make_w(slot, jj):
        return pltpu.make_async_copy(
            wq_hbm.at[:, pl.ds(jj * bn, bn)], w_ring.at[slot],
            w_sem.at[slot])

    def make_s(slot, jj):
        return pltpu.make_async_copy(
            sw_hbm.at[:, pl.ds(jj * bn, bn)], sw_ring.at[slot],
            s_sem.at[slot])

    finish = _ring_dmas(make_w, make_s, j, nj)

    @pl.when(j == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        q_ref[...] = _operand_from_q(q, mode)
        s_ref[...] = s

    slot = finish()
    acc = _operand_dot(q_ref[...], w_ring[slot], mode)
    o_ref[...] = (acc * s_ref[...] * sw_ring[slot]).astype(o_ref.dtype)


def _quant_dot_kernel_revisit(x_ref, mats_ref, wq_ref, sw_ref, o_ref, *,
                              n: int, mode: str, compute_dtype):
    """The PR-3 schedule, kept as the A/B baseline: EVERY grid step
    rotates + quantizes its row block before contracting -- d/block_n
    redundant transforms per row. Bitwise identical outputs to the
    rotate-once kernel (same math, different schedule)."""
    q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                  compute_dtype=compute_dtype)
    acc = _operand_dot(_operand_from_q(q, mode), wq_ref[...], mode)
    o_ref[...] = (acc * s * sw_ref[...]).astype(o_ref.dtype)


def _abft_check_col(op, cw):
    """The activation-side ABFT checksum of a dot-operand row block:
    ``chk[i] = sum_k op[i, k] * cw[k]`` with ``cw`` the precomputed
    column checksum of the DEQUANTIZED weight (``wquant.weight_checksum``),
    so ``sum_d y[i, d] == s[i] * chk[i]`` exactly in real arithmetic.
    Written as elementwise multiply + reduction -- NOT ``dot_general`` --
    so the rotate-once dot-placement contract (exactly one contraction
    dot per grid step, ``num_passes`` rotation dots in the j == 0 region)
    is untouched by verification. op: (bm, n) scratch operand, cw: (1, n)
    f32 -> (bm, 1) f32."""
    return jnp.sum(op.astype(jnp.float32) * cw, axis=-1, keepdims=True)


def _quant_dot_kernel_rotate_once_abft(x_ref, mats_ref, wq_ref, sw_ref,
                                       cw_ref, o_ref, r_ref, q_ref, s_ref,
                                       chk_ref, acc_ref, *, n: int, mode: str,
                                       compute_dtype):
    """The rotate-once grid step with the ABFT checksum column riding
    INSIDE the same pallas_call (fusion contract intact). j == 0
    additionally stashes the activation checksum ``chk`` (one extra
    n-element reduction per row block) and zeroes the row's output-sum
    accumulator; every j folds the f32 PRE-CAST contribution's row sums
    into the accumulator and rewrites the residual output
    ``r = sum_d y_f32[i, :] - s[i] * chk[i]`` (j is sequential within
    each row block, so the final j's write -- the full-row residual --
    wins). The o_ref math is graph-identical to the unverified kernel:
    ABFT-on outputs are bitwise ABFT-off outputs."""

    @pl.when(pl.program_id(1) == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        op = _operand_from_q(q, mode)
        q_ref[...] = op
        s_ref[...] = s
        chk_ref[...] = _abft_check_col(op, cw_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    acc = _operand_dot(q_ref[...], wq_ref[...], mode)
    contrib = acc * s_ref[...] * sw_ref[...]
    o_ref[...] = contrib.astype(o_ref.dtype)
    acc_ref[...] += jnp.sum(contrib, axis=-1, keepdims=True)
    r_ref[...] = acc_ref[...] - s_ref[...] * chk_ref[...]


def _quant_dot_kernel_streamed_abft(x_ref, mats_ref, wq_hbm, sw_hbm, cw_ref,
                                    o_ref, r_ref, q_ref, s_ref, chk_ref,
                                    acc_ref, w_ring, sw_ring, w_sem, s_sem,
                                    *, n: int, mode: str, compute_dtype,
                                    bn: int, nj: int):
    """Streamed grid step + ABFT. The column checksum ``cw_ref`` rides as
    a plain VMEM BlockSpec input OUTSIDE the DMA ring on purpose: the
    residual then compares ring-delivered weight tiles against a
    checksum that never travelled through the ring, so a mis-DMA'd or
    clobbered tile (the riskiest failure of this schedule) is exactly
    what trips it."""
    j = pl.program_id(1)

    def make_w(slot, jj):
        return pltpu.make_async_copy(
            wq_hbm.at[:, pl.ds(jj * bn, bn)], w_ring.at[slot],
            w_sem.at[slot])

    def make_s(slot, jj):
        return pltpu.make_async_copy(
            sw_hbm.at[:, pl.ds(jj * bn, bn)], sw_ring.at[slot],
            s_sem.at[slot])

    finish = _ring_dmas(make_w, make_s, j, nj)

    @pl.when(j == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        op = _operand_from_q(q, mode)
        q_ref[...] = op
        s_ref[...] = s
        chk_ref[...] = _abft_check_col(op, cw_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    slot = finish()
    acc = _operand_dot(q_ref[...], w_ring[slot], mode)
    contrib = acc * s_ref[...] * sw_ring[slot]
    o_ref[...] = contrib.astype(o_ref.dtype)
    acc_ref[...] += jnp.sum(contrib, axis=-1, keepdims=True)
    r_ref[...] = acc_ref[...] - s_ref[...] * chk_ref[...]


def _quant_dot_kernel_revisit_abft(x_ref, mats_ref, wq_ref, sw_ref, cw_ref,
                                   o_ref, r_ref, acc_ref, *, n: int,
                                   mode: str, compute_dtype):
    """Revisit grid step + ABFT: the transform recompute is deterministic
    (same f32-grid values every j), so q/s/chk are simply recomputed per
    step and only the output-sum accumulator needs scratch (zeroed at
    j == 0 -- j is sequential under the 'arbitrary' grid semantics)."""
    j = pl.program_id(1)
    q, s = _rotate_quantize_block(x_ref[...], mats_ref, n=n, mode=mode,
                                  compute_dtype=compute_dtype)
    op = _operand_from_q(q, mode)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    acc = _operand_dot(op, wq_ref[...], mode)
    contrib = acc * s * sw_ref[...]
    o_ref[...] = contrib.astype(o_ref.dtype)
    acc_ref[...] += jnp.sum(contrib, axis=-1, keepdims=True)
    r_ref[...] = acc_ref[...] - s * _abft_check_col(op, cw_ref[...])


def _stream_interpret_forced() -> bool:
    return os.environ.get(STREAM_INTERPRET_ENV, "").lower() in (
        "1", "true", "force")


def _resolve_schedule(schedule, interpret: bool = False) -> str:
    """Resolve the grid schedule: explicit argument, then the
    ``REPRO_QUANT_DOT_SCHEDULE`` env override, then ``rotate_once`` (the
    default until the bench gate shows the streamed win on hardware).

    ``streamed`` needs a real DMA engine; under ``interpret=True`` (the
    CPU: ``jaxapi.interpret_mode`` never picks it on a TPU, so the chip
    runs the schedule it is asked for) it degrades to ``rotate_once`` --
    warned once per
    process, counted in ``TRACE_COUNTS[("quant_dot", "stream_fallback")]``
    on every dispatch -- unless ``REPRO_QUANT_DOT_STREAM_INTERPRET`` is
    set, which runs the real streamed body on the interpreter's
    synchronous DMA simulation (the parity-test / bench hook)."""
    if schedule is None:
        schedule = os.environ.get(SCHEDULE_ENV_VAR) or "rotate_once"
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown quant_dot schedule {schedule!r}; expected one of "
            f"{SCHEDULES}")
    if schedule == "streamed" and interpret and not _stream_interpret_forced():
        warn_once(
            ("quant_dot", "stream_fallback"),
            "quant_dot schedule 'streamed' requires a real DMA engine; "
            "interpret mode falls back to 'rotate_once' (same outputs, "
            "no async weight prefetch). Set "
            f"{STREAM_INTERPRET_ENV}=1 to run the streamed kernel on "
            "the interpreter's synchronous DMA simulation. (warned "
            "once per process; TRACE_COUNTS[('quant_dot', "
            "'stream_fallback')] keeps counting)")
        return "rotate_once"
    return schedule


def pallas_quant_dot(x, wq, sw, plan, interpret: bool, schedule=None,
                     block_n=None, check=None):
    """Fused single-kernel rotate+quantize+GEMM over a 2D Pallas grid.

    x: (..., n) with n == plan.p (power of 2); wq: (n, d) storage-dtype
    weight; sw: (1, d) or (d,) f32 per-out-channel scales. Returns
    (..., d) in the plan's io dtype.

    ``schedule`` selects the grid schedule (default ``"rotate_once"``,
    overridable via ``REPRO_QUANT_DOT_SCHEDULE``; ``"streamed"`` under
    interpret mode degrades to ``rotate_once`` -- see
    ``_resolve_schedule``); ``block_n`` pins the out-channel tile
    (benchmark A/Bs hold the revisit count fixed with it). Both are
    static.

    ``check`` (the QTensor's precomputed (1, n) f32 ABFT column checksum,
    ``wquant.weight_checksum``) switches to the checksum-verified kernel
    variant: the SAME single pallas_call additionally emits a per-row f32
    residual ``r[i] = sum_d y_f32[i, :] - s[i] * (q[i, :] . check)`` --
    float-rounding small when healthy, shifted by any silent weight /
    DMA / accumulation corruption -- and the return value becomes
    ``(out, resid)`` with resid shaped (..., 1). Output math is
    graph-identical either way (``out`` is bitwise the check=None
    result); ``verify.residual_ok`` turns resid into a verdict.
    """
    sched = _resolve_schedule(schedule, interpret)
    if check is None:
        return _pallas_quant_dot(x, wq, sw, plan, interpret, sched, block_n)
    return _pallas_quant_dot_abft(x, wq, sw, check, plan, interpret, sched,
                                  block_n)


@functools.partial(jax.jit, static_argnames=("plan", "interpret", "schedule",
                                             "block_n"))
def _pallas_quant_dot(x, wq, sw, plan, interpret: bool, schedule: str,
                      block_n):
    TRACE_COUNTS[("pallas", "quant_dot")] += 1
    n = plan.p
    mode = plan.epilogue.mode
    cd = jnp.dtype(plan.compute_dtype)
    mats = _plan_mats(plan)
    lead = x.shape[:-1]
    x2, m = _rows(x, n)
    d = wq.shape[-1]
    sw2 = sw.reshape(1, d).astype(jnp.float32)
    bm, bn = quant_dot_blocks(n, d, m, x.dtype, cd, mode,
                              block_m=plan.block_m, block_n=block_n,
                              schedule=schedule)
    x2, _ = _pad_rows(x2, bm)
    pad_d = (-d) % bn
    if pad_d:
        wq2 = jnp.pad(wq, ((0, 0), (0, pad_d)))
        sw2 = jnp.pad(sw2, ((0, 0), (0, pad_d)))
    else:
        wq2 = wq
    mp, dp = x2.shape[0], d + pad_d
    common = dict(n=n, mode=mode, compute_dtype=cd)
    # rotate_once/revisit let the BlockSpec pipeline slice the weight;
    # streamed takes the weight movement over (ANY-memory-space refs, the
    # kernel DMAs each tile into its two-slot VMEM ring)
    wq_spec = pl.BlockSpec((n, bn), lambda i, j: (0, j))
    sw_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    if schedule == "rotate_once":
        kernel = functools.partial(_quant_dot_kernel_rotate_once, **common)
        scratch = [pltpu.VMEM((bm, n), _scratch_dtype(mode)),
                   pltpu.VMEM((bm, 1), jnp.float32)]
    elif schedule == "streamed":
        kernel = functools.partial(_quant_dot_kernel_streamed, **common,
                                   bn=bn, nj=dp // bn)
        scratch = [pltpu.VMEM((bm, n), _scratch_dtype(mode)),
                   pltpu.VMEM((bm, 1), jnp.float32),
                   pltpu.VMEM((2, n, bn), wq2.dtype),      # weight ring
                   pltpu.VMEM((2, 1, bn), jnp.float32),    # scale ring
                   pltpu.SemaphoreType.DMA((2,)),          # weight sems
                   pltpu.SemaphoreType.DMA((2,))]          # scale sems
        wq_spec = pl.BlockSpec(memory_space=ANY)
        sw_spec = pl.BlockSpec(memory_space=ANY)
    else:
        kernel = functools.partial(_quant_dot_kernel_revisit, **common)
        scratch = []
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, dp // bn),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i, j: (i, 0)),
            pl.BlockSpec((mats.shape[0],) + mats.shape[1:],
                         lambda i, j: (0, 0, 0)),
            wq_spec,
            sw_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), jnp.dtype(plan.dtype)),
        scratch_shapes=scratch,
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(x2, mats, wq2, sw2)
    return out[:m, :d].reshape(*lead, d)


def _scratch_dtype(mode: str):
    return jnp.int8 if QSPECS[mode][2] else jnp.bfloat16


@functools.partial(jax.jit, static_argnames=("plan", "interpret", "schedule",
                                             "block_n"))
def _pallas_quant_dot_abft(x, wq, sw, cw, plan, interpret: bool,
                           schedule: str, block_n):
    """The checksum-verified twin of :func:`_pallas_quant_dot`: same
    grid, same specs plus the block-constant (1, n) f32 checksum input
    and the (mp, 1) f32 residual output (its (bm, 1) tile at index
    (i, 0) is revisited across the sequential j axis -- the standard
    accumulator-output pattern; the final j's write is the full-row
    residual). Kept a separate traced function so the unverified path's
    jaxpr -- what the lint contracts and bitwise-parity suites pin --
    is untouched by construction."""
    TRACE_COUNTS[("pallas", "quant_dot")] += 1
    TRACE_COUNTS[("abft", "kernel_resid_trace")] += 1
    n = plan.p
    mode = plan.epilogue.mode
    cd = jnp.dtype(plan.compute_dtype)
    mats = _plan_mats(plan)
    lead = x.shape[:-1]
    x2, m = _rows(x, n)
    d = wq.shape[-1]
    sw2 = sw.reshape(1, d).astype(jnp.float32)
    cw2 = cw.reshape(1, n).astype(jnp.float32)
    bm, bn = quant_dot_blocks(n, d, m, x.dtype, cd, mode,
                              block_m=plan.block_m, block_n=block_n,
                              schedule=schedule, abft=True)
    x2, _ = _pad_rows(x2, bm)
    pad_d = (-d) % bn
    if pad_d:
        wq2 = jnp.pad(wq, ((0, 0), (0, pad_d)))
        sw2 = jnp.pad(sw2, ((0, 0), (0, pad_d)))
    else:
        wq2 = wq
    mp, dp = x2.shape[0], d + pad_d
    common = dict(n=n, mode=mode, compute_dtype=cd)
    wq_spec = pl.BlockSpec((n, bn), lambda i, j: (0, j))
    sw_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    # chk + acc f32 columns live across the j loop beside q/s
    verify_scratch = [pltpu.VMEM((bm, n), _scratch_dtype(mode)),
                      pltpu.VMEM((bm, 1), jnp.float32),
                      pltpu.VMEM((bm, 1), jnp.float32),    # chk
                      pltpu.VMEM((bm, 1), jnp.float32)]    # acc
    if schedule == "rotate_once":
        kernel = functools.partial(_quant_dot_kernel_rotate_once_abft,
                                   **common)
        scratch = verify_scratch
    elif schedule == "streamed":
        kernel = functools.partial(_quant_dot_kernel_streamed_abft, **common,
                                   bn=bn, nj=dp // bn)
        scratch = verify_scratch + [
            pltpu.VMEM((2, n, bn), wq2.dtype),      # weight ring
            pltpu.VMEM((2, 1, bn), jnp.float32),    # scale ring
            pltpu.SemaphoreType.DMA((2,)),          # weight sems
            pltpu.SemaphoreType.DMA((2,))]          # scale sems
        wq_spec = pl.BlockSpec(memory_space=ANY)
        sw_spec = pl.BlockSpec(memory_space=ANY)
    else:
        kernel = functools.partial(_quant_dot_kernel_revisit_abft, **common)
        scratch = [pltpu.VMEM((bm, 1), jnp.float32)]        # acc only
    out, resid = pl.pallas_call(
        kernel,
        grid=(mp // bm, dp // bn),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i, j: (i, 0)),
            pl.BlockSpec((mats.shape[0],) + mats.shape[1:],
                         lambda i, j: (0, 0, 0)),
            wq_spec,
            sw_spec,
            pl.BlockSpec((1, n), lambda i, j: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                   pl.BlockSpec((bm, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, dp), jnp.dtype(plan.dtype)),
                   jax.ShapeDtypeStruct((mp, 1), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(x2, mats, wq2, sw2, cw2)
    return (out[:m, :d].reshape(*lead, d),
            resid[:m].reshape(*lead, 1))


def _quant_dot_experts_kernel(x_ref, mats_ref, wq_ref, sw_ref, o_ref,
                              q_ref, s_ref, *, n: int, mode: str,
                              compute_dtype):
    """Rotate-once grid step on the 3-D (expert, row blocks, out-channel
    blocks) grid: identical to the dense kernel except every ref carries
    a leading per-expert axis of 1. j (innermost) is sequential, so the
    scratch written at j == 0 serves every weight tile of that
    (expert, row block) pair."""

    @pl.when(pl.program_id(2) == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[0], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        q_ref[...] = _operand_from_q(q, mode)
        s_ref[...] = s

    acc = _operand_dot(q_ref[...], wq_ref[0], mode)
    o_ref[0] = (acc * s_ref[...] * sw_ref[0]).astype(o_ref.dtype)


def _quant_dot_experts_kernel_streamed(x_ref, mats_ref, wq_hbm, sw_hbm,
                                       o_ref, q_ref, s_ref, w_ring, sw_ring,
                                       w_sem, s_sem, *, n: int, mode: str,
                                       compute_dtype, bn: int, nj: int):
    """Streamed grid step on the 3-D (expert, row blocks, out-channel
    blocks) grid: the dense streamed kernel with the DMA sources indexed
    by the CURRENT expert (``wq_hbm``/``sw_hbm`` stay whole (E, n, d) /
    (E, 1, d) ANY-memory-space refs; each copy slices expert e's tile
    j). j restarts at every (expert, row block) pair, so the warm-up
    re-primes slot 0 and the ring parity resets -- and the ``j + 1 < nj``
    prefetch guard guarantees no copy is in flight across the pair
    boundary."""
    e, j = pl.program_id(0), pl.program_id(2)

    def make_w(slot, jj):
        return pltpu.make_async_copy(
            wq_hbm.at[e, :, pl.ds(jj * bn, bn)], w_ring.at[slot],
            w_sem.at[slot])

    def make_s(slot, jj):
        return pltpu.make_async_copy(
            sw_hbm.at[e, :, pl.ds(jj * bn, bn)], sw_ring.at[slot],
            s_sem.at[slot])

    finish = _ring_dmas(make_w, make_s, j, nj)

    @pl.when(j == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[0], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        q_ref[...] = _operand_from_q(q, mode)
        s_ref[...] = s

    slot = finish()
    acc = _operand_dot(q_ref[...], w_ring[slot], mode)
    o_ref[0] = (acc * s_ref[...] * sw_ring[slot]).astype(o_ref.dtype)


def _quant_dot_experts_kernel_abft(x_ref, mats_ref, wq_ref, sw_ref, cw_ref,
                                   o_ref, r_ref, q_ref, s_ref, chk_ref,
                                   acc_ref, *, n: int, mode: str,
                                   compute_dtype):
    """Rotate-once 3-D expert grid step + ABFT: the dense verified
    kernel with every ref carrying a leading per-expert axis of 1 and
    the checksum tile sliced per CURRENT expert. j restarts per
    (expert, row block), so the j == 0 re-stash also re-zeroes the
    accumulator and re-derives chk against that expert's checksum."""

    @pl.when(pl.program_id(2) == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[0], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        op = _operand_from_q(q, mode)
        q_ref[...] = op
        s_ref[...] = s
        chk_ref[...] = _abft_check_col(op, cw_ref[0])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    acc = _operand_dot(q_ref[...], wq_ref[0], mode)
    contrib = acc * s_ref[...] * sw_ref[0]
    o_ref[0] = contrib.astype(o_ref.dtype)
    acc_ref[...] += jnp.sum(contrib, axis=-1, keepdims=True)
    r_ref[0] = acc_ref[...] - s_ref[...] * chk_ref[...]


def _quant_dot_experts_kernel_streamed_abft(x_ref, mats_ref, wq_hbm, sw_hbm,
                                            cw_ref, o_ref, r_ref, q_ref,
                                            s_ref, chk_ref, acc_ref, w_ring,
                                            sw_ring, w_sem, s_sem, *, n: int,
                                            mode: str, compute_dtype,
                                            bn: int, nj: int):
    """Streamed 3-D expert grid step + ABFT: DMA ring per (expert, row
    block) exactly as the unverified streamed kernel; the per-expert
    checksum tile arrives through the plain BlockSpec pipeline (outside
    the ring) so ring mis-delivery is detectable."""
    e, j = pl.program_id(0), pl.program_id(2)

    def make_w(slot, jj):
        return pltpu.make_async_copy(
            wq_hbm.at[e, :, pl.ds(jj * bn, bn)], w_ring.at[slot],
            w_sem.at[slot])

    def make_s(slot, jj):
        return pltpu.make_async_copy(
            sw_hbm.at[e, :, pl.ds(jj * bn, bn)], sw_ring.at[slot],
            s_sem.at[slot])

    finish = _ring_dmas(make_w, make_s, j, nj)

    @pl.when(j == 0)
    def _rotate():
        q, s = _rotate_quantize_block(x_ref[0], mats_ref, n=n, mode=mode,
                                      compute_dtype=compute_dtype)
        op = _operand_from_q(q, mode)
        q_ref[...] = op
        s_ref[...] = s
        chk_ref[...] = _abft_check_col(op, cw_ref[0])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    slot = finish()
    acc = _operand_dot(q_ref[...], w_ring[slot], mode)
    contrib = acc * s_ref[...] * sw_ring[slot]
    o_ref[0] = contrib.astype(o_ref.dtype)
    acc_ref[...] += jnp.sum(contrib, axis=-1, keepdims=True)
    r_ref[0] = acc_ref[...] - s_ref[...] * chk_ref[...]


def pallas_quant_dot_experts(x, wq, sw, plan, interpret: bool,
                             schedule=None, block_n=None, check=None):
    """Fused rotate+quantize+GEMM for stacked expert weights: ONE kernel
    over a 3-D (expert, row blocks, out-channel blocks) grid with the
    rotate-once schedule per (expert, row block) -- replacing the PR-4
    split into a fused rotate+quantize kernel plus a per-expert XLA
    einsum (which round-tripped (q, scales) through HBM).

    x: (..., E, c, n) dispatched activations; wq: (E, n, d) storage-dtype
    expert weights; sw: (E, 1, d) f32 per-(expert, out-channel) scales.
    Returns (..., E, c, d) in the plan's io dtype.

    ``schedule``/``block_n``/``check`` behave exactly as in
    :func:`pallas_quant_dot` (the streamed DMA ring applies per
    (expert, row block) pair; ``check`` is the stacked (E, 1, n) f32
    per-expert column checksum and makes the return value
    ``(out, resid)`` with resid shaped (..., E, c, 1)).
    """
    sched = _resolve_schedule(schedule, interpret)
    if check is None:
        return _pallas_quant_dot_experts(x, wq, sw, plan, interpret, sched,
                                         block_n)
    return _pallas_quant_dot_experts_abft(x, wq, sw, check, plan, interpret,
                                          sched, block_n)


@functools.partial(jax.jit, static_argnames=("plan", "interpret", "schedule",
                                             "block_n"))
def _pallas_quant_dot_experts(x, wq, sw, plan, interpret: bool,
                              schedule: str, block_n):
    TRACE_COUNTS[("pallas", "quant_dot_experts")] += 1
    n = plan.p
    mode = plan.epilogue.mode
    cd = jnp.dtype(plan.compute_dtype)
    mats = _plan_mats(plan)
    E, _, d = wq.shape
    lead, cap = x.shape[:-3], x.shape[-2]
    # rows of one expert contiguous: (..., E, c, n) -> (E, rows, n)
    x3 = jnp.moveaxis(x.reshape(-1, E, cap, n), 1, 0).reshape(E, -1, n)
    m = x3.shape[1]
    sw3 = sw.reshape(E, 1, d).astype(jnp.float32)
    bm, bn = quant_dot_blocks(n, d, m, x.dtype, cd, mode,
                              block_m=plan.block_m, block_n=block_n,
                              schedule=schedule)
    pad_m, pad_d = (-m) % bm, (-d) % bn
    if pad_m:
        x3 = jnp.pad(x3, ((0, 0), (0, pad_m), (0, 0)))
    wq3 = wq
    if pad_d:
        wq3 = jnp.pad(wq, ((0, 0), (0, 0), (0, pad_d)))
        sw3 = jnp.pad(sw3, ((0, 0), (0, 0), (0, pad_d)))
    mp, dp = m + pad_m, d + pad_d
    scratch = [pltpu.VMEM((bm, n), _scratch_dtype(mode)),
               pltpu.VMEM((bm, 1), jnp.float32)]
    wq_spec = pl.BlockSpec((1, n, bn), lambda e, i, j: (e, 0, j))
    sw_spec = pl.BlockSpec((1, 1, bn), lambda e, i, j: (e, 0, j))
    if schedule == "streamed":
        kernel = functools.partial(_quant_dot_experts_kernel_streamed,
                                   n=n, mode=mode, compute_dtype=cd,
                                   bn=bn, nj=dp // bn)
        scratch += [pltpu.VMEM((2, n, bn), wq3.dtype),     # weight ring
                    pltpu.VMEM((2, 1, bn), jnp.float32),   # scale ring
                    pltpu.SemaphoreType.DMA((2,)),         # weight sems
                    pltpu.SemaphoreType.DMA((2,))]         # scale sems
        wq_spec = pl.BlockSpec(memory_space=ANY)
        sw_spec = pl.BlockSpec(memory_space=ANY)
    else:
        # revisit never grew a 3-D body (the A/B baseline is 2-D only):
        # anything else runs the rotate-once step
        kernel = functools.partial(_quant_dot_experts_kernel, n=n,
                                   mode=mode, compute_dtype=cd)
    out = pl.pallas_call(
        kernel,
        grid=(E, mp // bm, dp // bn),
        in_specs=[
            pl.BlockSpec((1, bm, n), lambda e, i, j: (e, i, 0)),
            pl.BlockSpec((mats.shape[0],) + mats.shape[1:],
                         lambda e, i, j: (0, 0, 0)),
            wq_spec,
            sw_spec,
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, mp, dp), jnp.dtype(plan.dtype)),
        scratch_shapes=scratch,
        compiler_params=tpu_compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x3, mats, wq3, sw3)
    out = jnp.moveaxis(out[:, :m, :d].reshape(E, -1, cap, d), 0, 1)
    return out.reshape(*lead, E, cap, d)


@functools.partial(jax.jit, static_argnames=("plan", "interpret", "schedule",
                                             "block_n"))
def _pallas_quant_dot_experts_abft(x, wq, sw, cw, plan, interpret: bool,
                                   schedule: str, block_n):
    """The checksum-verified twin of :func:`_pallas_quant_dot_experts`
    (see ``_pallas_quant_dot_abft`` for why it is a separate traced
    function): per-expert (1, 1, n) checksum tiles, (E, mp, 1) residual
    output revisited across the sequential j axis."""
    TRACE_COUNTS[("pallas", "quant_dot_experts")] += 1
    TRACE_COUNTS[("abft", "kernel_resid_trace")] += 1
    n = plan.p
    mode = plan.epilogue.mode
    cd = jnp.dtype(plan.compute_dtype)
    mats = _plan_mats(plan)
    E, _, d = wq.shape
    lead, cap = x.shape[:-3], x.shape[-2]
    x3 = jnp.moveaxis(x.reshape(-1, E, cap, n), 1, 0).reshape(E, -1, n)
    m = x3.shape[1]
    sw3 = sw.reshape(E, 1, d).astype(jnp.float32)
    cw3 = cw.reshape(E, 1, n).astype(jnp.float32)
    bm, bn = quant_dot_blocks(n, d, m, x.dtype, cd, mode,
                              block_m=plan.block_m, block_n=block_n,
                              schedule=schedule, abft=True)
    pad_m, pad_d = (-m) % bm, (-d) % bn
    if pad_m:
        x3 = jnp.pad(x3, ((0, 0), (0, pad_m), (0, 0)))
    wq3 = wq
    if pad_d:
        wq3 = jnp.pad(wq, ((0, 0), (0, 0), (0, pad_d)))
        sw3 = jnp.pad(sw3, ((0, 0), (0, 0), (0, pad_d)))
    mp, dp = m + pad_m, d + pad_d
    scratch = [pltpu.VMEM((bm, n), _scratch_dtype(mode)),
               pltpu.VMEM((bm, 1), jnp.float32),
               pltpu.VMEM((bm, 1), jnp.float32),     # chk
               pltpu.VMEM((bm, 1), jnp.float32)]     # acc
    wq_spec = pl.BlockSpec((1, n, bn), lambda e, i, j: (e, 0, j))
    sw_spec = pl.BlockSpec((1, 1, bn), lambda e, i, j: (e, 0, j))
    if schedule == "streamed":
        kernel = functools.partial(_quant_dot_experts_kernel_streamed_abft,
                                   n=n, mode=mode, compute_dtype=cd,
                                   bn=bn, nj=dp // bn)
        scratch += [pltpu.VMEM((2, n, bn), wq3.dtype),     # weight ring
                    pltpu.VMEM((2, 1, bn), jnp.float32),   # scale ring
                    pltpu.SemaphoreType.DMA((2,)),         # weight sems
                    pltpu.SemaphoreType.DMA((2,))]         # scale sems
        wq_spec = pl.BlockSpec(memory_space=ANY)
        sw_spec = pl.BlockSpec(memory_space=ANY)
    else:
        kernel = functools.partial(_quant_dot_experts_kernel_abft, n=n,
                                   mode=mode, compute_dtype=cd)
    out, resid = pl.pallas_call(
        kernel,
        grid=(E, mp // bm, dp // bn),
        in_specs=[
            pl.BlockSpec((1, bm, n), lambda e, i, j: (e, i, 0)),
            pl.BlockSpec((mats.shape[0],) + mats.shape[1:],
                         lambda e, i, j: (0, 0, 0)),
            wq_spec,
            sw_spec,
            pl.BlockSpec((1, 1, n), lambda e, i, j: (e, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bm, bn), lambda e, i, j: (e, i, j)),
                   pl.BlockSpec((1, bm, 1), lambda e, i, j: (e, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((E, mp, dp), jnp.dtype(plan.dtype)),
                   jax.ShapeDtypeStruct((E, mp, 1), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=tpu_compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x3, mats, wq3, sw3, cw3)
    out = jnp.moveaxis(out[:, :m, :d].reshape(E, -1, cap, d), 0, 1)
    r = jnp.moveaxis(resid[:, :m].reshape(E, -1, cap, 1), 0, 1)
    return out.reshape(*lead, E, cap, d), r.reshape(*lead, E, cap, 1)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def xla_quant_dot_resid(x, wq, sw, cw, plan, interpret: bool):
    """The unfused ABFT residual oracle for dispatches that do not run
    the fused kernel (xla backend, above-cap sizes): re-derive the
    rotated/quantized activation with the SAME transform+quantize ops as
    :func:`xla_quant_dot`, recompute the weight's column checksum from
    the LIVE weight with the exact ``wquant.weight_checksum`` op order,
    and contract the activation against the checksum DIFFERENCE:

        resid = s * (q . (recomputed_cw - stored_cw))

    Healthy weights make the difference bitwise zero (same arrays, same
    reduction), so the residual is exactly 0.0 per row; any mutation of
    ``wq``/``sw`` since quantize time shows up as the corruption
    magnitude times the activation row. Costs one extra transform of x
    -- the documented price of verifying the path that cannot carry the
    in-kernel checksum column. Returns (..., 1) f32."""
    from repro.core.api import _dispatch_transform, _strip

    TRACE_COUNTS[("abft", "xla_resid_trace")] += 1
    n, d = wq.shape
    # Same transform dispatch as the unfused oracle (grouped plans block
    # the rotation over p-wide groups; a flat reshape would be wrong).
    y = _dispatch_transform(x, _strip(plan), interpret)
    epi = plan.epilogue
    q, s = _quantize_rows(y.astype(jnp.float32), epi.mode,
                          axis=-1 if epi.per_token else None)
    sw2 = sw.reshape(1, d).astype(jnp.float32)
    cwt = (wq.astype(jnp.float32) * sw2).sum(axis=-1)
    dvec = cwt - cw.reshape(n)
    resid = jnp.einsum("...k,k->...", q.astype(jnp.float32), dvec)[..., None]
    return jnp.asarray(s, jnp.float32) * resid


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def xla_quant_dot(x, wq, sw, plan, interpret: bool):
    """Unfused oracle semantics on the factored XLA path: rotate, quantize
    per token, then the SAME ``epilogue_dot`` contraction (int8/int32 or
    fp8-in-bf16/f32). Shards trivially under pjit -- the fallback for
    sizes above the kernel cap and the ground truth the fused kernel is
    tested against."""
    TRACE_COUNTS[("xla", "quant_dot")] += 1
    y = _xla_transform(x, plan)
    q, s = _quantize_rows(y.astype(jnp.float32), mode=plan.epilogue.mode)
    return epilogue_dot(q, s, wq, sw.reshape(1, wq.shape[-1]),
                        plan.epilogue.mode, jnp.dtype(plan.dtype))
