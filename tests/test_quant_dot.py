"""The fused rotate->quantize->GEMM consumer path (DESIGN.md section 6):
quant_dot against the unfused ``quantize(hadamard(x)) @ quantize(w)``
oracle across modes x dtypes x pow2/non-pow2 sizes, single-kernel
lowering of the model hot path, compute-dtype-aware plans (native bf16
passes + honest VMEM accounting), STE gradients, no-retrace plan
caching, and the deprecation shims."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.api import (
    QuantEpilogue,
    hadamard,
    plan_for,
    quant_dot,
)
from repro.core.hadamard import resolve_compute_dtype
from repro.core.quant import QuantConfig, quantize
from repro.core.rotations import rotated_quant_dot, rotated_quant_dot_experts
from repro.core.wquant import quantize_weight
from repro.kernels import registry
from repro.kernels.registry import default_block_m

MODES = ("int8", "fp8_e4m3", "fp8_e5m2")
# contraction-rounding tolerance vs. the fake-quant oracle (the oracle
# rounds dequantized operands to the io dtype before its matmul; the real
# path contracts exactly on the int8/fp8 grid and scales afterwards)
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 5e-2, jnp.float16: 1e-2}


def _x(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


def _oracle(x, w, mode, backend):
    """The unfused reference the issue names: fake-quantize the rotated
    activation per token and the weight per out-channel, then matmul."""
    xq = quantize(hadamard(x, backend=backend), mode, axis=-1)
    wq = quantize(w, mode, axis=0)
    return xq @ wq


def _rel_err(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)


# --------------------------------------------------------------- oracle
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("n", [256, 384])  # pow2 (fused) and 3*128 (grouped)
def test_quant_dot_matches_unfused_oracle(mode, dtype, n):
    x = _x((9, n), seed=n, dtype=dtype)
    w = _x((n, 160), seed=n + 1, dtype=dtype) * 0.05
    out = quant_dot(x, w, mode=mode, backend="pallas")
    want = _oracle(x, w, mode, backend="pallas")
    assert out.shape == (9, 160) and out.dtype == x.dtype
    assert _rel_err(out, want) < TOL[dtype]


@settings(deadline=None, max_examples=8)
@given(logn=st.integers(5, 10), seed=st.integers(0, 2**31 - 1),
       mode=st.sampled_from(MODES))
def test_property_quant_dot_pow2(logn, seed, mode):
    n = 2 ** logn
    x = _x((5, n), seed=seed)
    w = _x((n, 96), seed=seed + 1) * 0.1
    out = quant_dot(x, w, mode=mode, backend="pallas")
    assert _rel_err(out, _oracle(x, w, mode, "pallas")) < 1e-3


@settings(deadline=None, max_examples=6)
@given(g=st.integers(3, 7), logp=st.integers(4, 7),
       seed=st.integers(0, 2**31 - 1))
def test_property_quant_dot_grouped(g, logp, seed):
    n = g * 2 ** logp  # non-power-of-2: unfused fallback, grouped rotate
    if n & (n - 1) == 0:
        n += 2 ** logp  # g even could make a pow2; keep it grouped
    x = _x((4, n), seed=seed)
    w = _x((n, 64), seed=seed + 1) * 0.1
    out = quant_dot(x, w, mode="int8")
    xq = quantize(hadamard(x), "int8", axis=-1)
    want = xq @ quantize(w, "int8", axis=0)
    assert _rel_err(out, want) < 1e-3


def test_prequantized_weights_match_on_the_fly():
    x = _x((7, 512), seed=3)
    w = _x((512, 128), seed=4) * 0.05
    for mode in MODES:
        a = quant_dot(x, w, mode=mode, backend="pallas")
        b = quant_dot(x, quantize_weight(w, mode), mode=mode,
                      backend="pallas")
        assert (np.asarray(a) == np.asarray(b)).all()


def test_pallas_and_xla_backends_agree_bitwise():
    x = _x((11, 1024), seed=5)
    w = _x((1024, 192), seed=6) * 0.05
    for mode in MODES:
        a = quant_dot(x, w, mode=mode, backend="pallas")
        b = quant_dot(x, w, mode=mode, backend="xla")
        # same epilogue math, same exact low-precision contraction
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


# ----------------------------------------------------------- validation
def test_quant_dot_plan_validation():
    x = _x((4, 256))
    w = _x((256, 64))
    with pytest.raises(ValueError, match="non-dequant"):
        quant_dot(x, w, plan_for(256))  # no epilogue
    with pytest.raises(ValueError, match="non-dequant"):
        quant_dot(x, w, plan_for(
            256, epilogue=QuantEpilogue("int8", dequant=True)))
    with pytest.raises(ValueError, match="explicit plan"):
        quant_dot(x, w, plan_for(256, epilogue=QuantEpilogue("int8")),
                  mode="int8")
    with pytest.raises(ValueError, match="contraction dim"):
        quant_dot(x, _x((128, 64)), mode="int8")
    with pytest.raises(ValueError, match="dtype"):
        quant_dot(_x((4, 256), dtype=jnp.bfloat16), w,
                  plan_for(256, epilogue=QuantEpilogue("int8")))
    with pytest.raises(ValueError, match="storage dtype"):
        quant_dot(x, quantize_weight(w, "fp8_e4m3"), mode="int8")


def test_qd_fusability_vmem_budget_guard():
    """fp8 weight tiles cost two storage slots, the bf16 operand and a
    K-slice of f32 conversion in VMEM: at the n=2^15 kernel cap even the
    minimal (n, 128) tile busts the budget, so the plan must take the
    unfused fallback; int8 (two storage slots) still fuses."""
    from repro.core.api import _qd_fusable

    assert _qd_fusable(
        plan_for(32768, backend="pallas", epilogue=QuantEpilogue("int8")))
    assert not _qd_fusable(
        plan_for(32768, backend="pallas",
                 epilogue=QuantEpilogue("fp8_e4m3")))
    assert _qd_fusable(
        plan_for(4096, backend="pallas",
                 epilogue=QuantEpilogue("fp8_e4m3")))


# ---------------------------------------------------- single-kernel HLO
# the structural walkers live in repro.analysis (shared with the lint
# rules); the tests assert through the same implementation CI lints with
from repro.analysis import count_pallas_calls as _count_pallas_calls


def test_rotated_quant_dot_lowers_to_single_pallas_call():
    """Acceptance: pallas + int8 + pow2 n is ONE pallas_call -- rotate,
    quantize AND the GEMM; no HBM round trip of the rotated tensor."""
    cfg = QuantConfig(mode="int8", rotate="hadamard", backend="pallas")
    x = _x((2, 4, 2048), seed=7)
    w = _x((2048, 256), seed=8) * 0.05
    jaxpr = jax.make_jaxpr(lambda a, b: rotated_quant_dot(a, b, cfg))(x, w)
    assert _count_pallas_calls(jaxpr.jaxpr) == 1
    # ... and the dot really happened inside it: no dot_general outside
    outer_dots = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name == "dot_general"]
    assert not outer_dots


def test_trace_counts_stable_for_quant_dot():
    cfg = QuantConfig(mode="int8", rotate="hadamard", backend="pallas")
    w = _x((512, 64), seed=9) * 0.1
    rotated_quant_dot(_x((8, 512)), w, cfg)  # warm
    key = ("pallas", "quant_dot")
    before = registry.TRACE_COUNTS[key]
    for seed in range(3):
        rotated_quant_dot(_x((8, 512), seed=seed), w, cfg)
    assert registry.TRACE_COUNTS[key] == before
    rotated_quant_dot(_x((4, 1024)), _x((1024, 64)) * 0.1, cfg)
    assert registry.TRACE_COUNTS[key] == before + 1


# ------------------------------------------------------------ autodiff
def test_quant_dot_ste_gradients():
    x = _x((6, 256), seed=11)
    w = _x((256, 96), seed=12) * 0.1
    g = _x((6, 96), seed=13)
    gx, gw = jax.grad(
        lambda a, b: jnp.sum(quant_dot(a, b, mode="int8",
                                       backend="pallas") * g),
        argnums=(0, 1))(x, w)
    # STE: out ~= had(x) @ w, so gx = had(g w^T), gw = had(x)^T g
    want_gx = hadamard(g @ w.T, backend="pallas")
    want_gw = hadamard(x, backend="pallas").T @ g
    np.testing.assert_allclose(np.asarray(gx), np.asarray(want_gx),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(want_gw),
                               rtol=1e-4, atol=1e-4)


def test_quant_dot_prequantized_weight_gets_no_gradient():
    x = _x((4, 256), seed=14)
    wq, sw = quantize_weight(_x((256, 32), seed=15) * 0.1, "fp8_e4m3")
    gx, gsw = jax.grad(
        lambda a, s: jnp.sum(quant_dot(a, (wq, s), mode="fp8_e4m3",
                                       backend="pallas") ** 2),
        argnums=(0, 1))(x, sw)
    assert bool(jnp.isfinite(gx).all()) and float(jnp.abs(gx).max()) > 0
    assert float(jnp.abs(gsw).max()) == 0.0  # scale is a statistic


# ------------------------------------------------- compute-dtype plans
def test_compute_dtype_resolution():
    assert resolve_compute_dtype(jnp.float32) == "float32"
    assert resolve_compute_dtype(jnp.bfloat16) == "bfloat16"
    assert resolve_compute_dtype(jnp.float16) == "float16"
    assert resolve_compute_dtype(jnp.bfloat16, jnp.float32) == "float32"
    with pytest.raises(ValueError):
        resolve_compute_dtype(jnp.float32, jnp.int8)
    assert plan_for(4096, dtype=jnp.bfloat16).compute_dtype == "bfloat16"
    assert plan_for(4096, dtype=jnp.float32).compute_dtype == "float32"
    # the override is part of the cache key
    p32 = plan_for(4096, dtype=jnp.bfloat16, compute_dtype=jnp.float32)
    assert p32.compute_dtype == "float32"
    assert p32 is not plan_for(4096, dtype=jnp.bfloat16)


def test_default_block_m_16bit_rows_at_least_1p5x_f32():
    """Acceptance: dropping the unconditional f32 VMEM copy buys 16-bit
    dtypes >= 1.5x larger row tiles at n=4096."""
    m = 1 << 16
    bm_f32 = default_block_m(4096, m, jnp.float32,
                             compute_dtype=jnp.float32)
    for dt in (jnp.bfloat16, jnp.float16):
        bm16 = default_block_m(4096, m, dt, compute_dtype=dt)
        assert bm16 >= 1.5 * bm_f32, (bm16, bm_f32)


def test_default_block_m_charges_epilogue_outputs():
    """The fused kernels' q tile + per-row scales are charged: the tile
    fits the documented 8 MiB budget for every epilogue form."""
    budget = 8 * 1024 * 1024
    m = 1 << 16
    for n in (4096, 16384, 32768):
        for epi in (None, QuantEpilogue("int8"),
                    QuantEpilogue("fp8_e4m3", dequant=True)):
            bm = default_block_m(n, m, jnp.float32,
                                 compute_dtype=jnp.float32, epilogue=epi)
            out_b = 4 if (epi is None or epi.dequant) else 1
            resident = bm * n * (4 + 4 + out_b) + (0 if epi is None else bm * 4)
            assert resident <= budget + n * 16  # one-row rounding slack


def test_bf16_compute_error_bound_vs_f32():
    """Appendix C mirror: native bf16 passes track the f32-compute
    transform within a small relative bound -- and differ from it
    (proving the low-precision path is actually taken)."""
    x = _x((32, 4096), seed=16, dtype=jnp.bfloat16)
    y16 = hadamard(x, plan_for(4096, dtype=jnp.bfloat16, backend="pallas"))
    y32 = hadamard(x, plan_for(4096, dtype=jnp.bfloat16, backend="pallas",
                               compute_dtype=jnp.float32))
    a16 = np.asarray(y16, np.float32)
    a32 = np.asarray(y32, np.float32)
    rel = np.abs(a16 - a32).max() / np.abs(a32).max()
    assert 0 < rel < 2e-2, rel
    # and the bf16 result still matches the exact rotation to bf16 accuracy
    want = np.asarray(hadamard(x.astype(jnp.float32)), np.float32)
    assert np.abs(a16 - want).max() / np.abs(want).max() < 2e-2


def test_quant_dot_bf16_no_retrace_and_correct():
    cfg = QuantConfig(mode="int8", rotate="hadamard", backend="pallas")
    x = _x((8, 512), seed=17, dtype=jnp.bfloat16)
    w = _x((512, 64), seed=18, dtype=jnp.bfloat16) * 0.1
    out = rotated_quant_dot(x, w, cfg)
    assert out.dtype == jnp.bfloat16
    key = ("pallas", "quant_dot")
    before = registry.TRACE_COUNTS[key]
    rotated_quant_dot(_x((8, 512), seed=19, dtype=jnp.bfloat16), w, cfg)
    assert registry.TRACE_COUNTS[key] == before


# ------------------------------------------------------------ MoE path
def test_rotated_quant_dot_experts_matches_per_expert_quant_dot():
    cfg = QuantConfig(mode="int8", rotate="hadamard", backend="pallas")
    x = _x((2, 3, 4, 256), seed=20)          # (B, E, cap, f)
    w = _x((3, 256, 64), seed=21) * 0.1      # (E, f, d)
    out = rotated_quant_dot_experts(x, w, cfg)
    assert out.shape == (2, 3, 4, 64)
    for e in range(3):
        want = quant_dot(x[:, e], w[e], mode="int8", backend="pallas")
        np.testing.assert_allclose(np.asarray(out[:, e]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    ge = jax.grad(lambda ww: jnp.sum(
        rotated_quant_dot_experts(x, ww, cfg) ** 2))(w)
    assert bool(jnp.isfinite(ge).all()) and float(jnp.abs(ge).max()) > 0


# -------------------------------------------- rotate-once grid schedule
from repro.analysis import dots_by_region as _dots_by_region
from repro.analysis import kernel_jaxpr as _kernel_jaxpr


@pytest.mark.parametrize("d", [256, 1024])
def test_rotate_once_transform_guarded_per_row_block(d):
    """Acceptance (structural): in the rotate-once kernel the transform
    matmuls are guarded by the j == 0 cond -- executed once per ROW BLOCK
    -- while exactly ONE top-level dot_general (the contraction) runs per
    out-channel tile; and the counts are independent of d (the revisit
    count d/block_n only changes the grid, never the per-block transform
    work)."""
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import pallas_quant_dot

    plan = plan_for(512, backend="pallas", epilogue=QuantEpilogue("int8"))
    x = _x((8, 512))
    wq = jnp.zeros((512, d), jnp.int8)
    sw = jnp.ones((1, d), jnp.float32)
    closed = jax.make_jaxpr(
        lambda a, q, s: pallas_quant_dot(a, q, s, plan, True,
                                         "rotate_once", 128))(x, wq, sw)
    top, in_cond = _dots_by_region(_kernel_jaxpr(closed))
    assert top == 1, top                       # the contraction only
    assert in_cond == plan.num_passes, (in_cond, plan.num_passes)

    # the PR-3 revisit schedule as contrast: every grid step recomputes
    # the passes unguarded -- passes + contraction all at top level
    closed_rv = jax.make_jaxpr(
        lambda a, q, s: pallas_quant_dot(a, q, s, plan, True,
                                         "revisit", 128))(x, wq, sw)
    top_rv, in_cond_rv = _dots_by_region(_kernel_jaxpr(closed_rv))
    assert top_rv == plan.num_passes + 1 and in_cond_rv == 0


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_rotate_once_bitwise_vs_revisit_schedule(mode, dtype):
    """Acceptance: the new schedule is bitwise the PR-3 kernel across all
    three quant modes x f32/bf16/fp16 -- with block_n pinned small so the
    out-channel loop really revisits (d / block_n = 5 tiles)."""
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import pallas_quant_dot

    x = _x((23, 512), seed=30, dtype=dtype)
    wq, sw = quantize_weight(_x((512, 640), seed=31, dtype=dtype) * 0.05,
                             mode)
    plan = plan_for(512, dtype=dtype, backend="pallas",
                    epilogue=QuantEpilogue(mode))
    a = pallas_quant_dot(x, wq, sw, plan, True, "rotate_once", 128)
    b = pallas_quant_dot(x, wq, sw, plan, True, "revisit", 128)
    assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_quant_dot_schedule_validation_and_env(monkeypatch):
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import SCHEDULE_ENV_VAR, pallas_quant_dot

    x = _x((4, 256))
    wq, sw = quantize_weight(_x((256, 64), seed=1) * 0.1, "int8")
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    with pytest.raises(ValueError, match="schedule"):
        pallas_quant_dot(x, wq, sw, plan, True, "typo")
    want = pallas_quant_dot(x, wq, sw, plan, True)
    monkeypatch.setenv(SCHEDULE_ENV_VAR, "revisit")
    got = pallas_quant_dot(x, wq, sw, plan, True)
    assert (np.asarray(got) == np.asarray(want)).all()
    monkeypatch.setenv(SCHEDULE_ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="schedule"):
        pallas_quant_dot(x, wq, sw, plan, True)


def test_quant_dot_blocks_pinned_block_m_drives_bn():
    """Satellite fix: a user-pinned block_m participates in the
    weight-tile/block_n tradeoff INSTEAD of being applied after the
    heuristic bm sizing -- a tiny pinned row tile frees VMEM, so the
    out-channel tile widens beyond what the default-bm sizing picks."""
    from repro.kernels.quant_dot import quant_dot_blocks

    args = (4096, 8192, 1 << 14, jnp.float32, jnp.float32, "fp8_e4m3")
    bm_def, bn_def = quant_dot_blocks(*args)
    bm_pin, bn_pin = quant_dot_blocks(*args, block_m=8)
    assert bm_pin == 8                      # the pin is honored verbatim
    assert bn_pin > bn_def, (bn_pin, bn_def)
    assert bn_pin % 128 == 0
    # and a pinned block_n is honored verbatim on both paths
    assert quant_dot_blocks(*args, block_n=256)[1] == 256
    assert quant_dot_blocks(*args, block_m=8, block_n=256) == (8, 256)


def test_quant_dot_pinned_block_m_end_to_end():
    """plan.block_m flows through the rotate-once kernel (scratch sized
    to the pin) and stays bitwise with the default tiling."""
    from repro.core.api import QuantEpilogue, plan_for, quant_dot

    x = _x((24, 512), seed=33)
    w = _x((512, 320), seed=34) * 0.05
    qt = quantize_weight(w, "int8")
    want = quant_dot(x, qt, mode="int8", backend="pallas")
    got = quant_dot(x, qt, plan_for(
        512, backend="pallas", epilogue=QuantEpilogue("int8"), block_m=8))
    assert (np.asarray(got) == np.asarray(want)).all()


# ------------------------------------------- fused 3-D expert kernel
from repro.analysis import dots_outside_pallas as _dots_outside_pallas


def test_quant_dot_experts_fused_single_kernel():
    """Off-mesh fusable expert plans run ONE pallas_call carrying every
    expert's rotation, quantization AND contraction -- no per-expert
    einsum outside the kernel (PR 4 split into a rotate+quantize kernel
    plus an XLA einsum that re-read (q, scales) from HBM)."""
    from repro.core.api import QuantEpilogue, plan_for, quant_dot_experts

    x = _x((2, 3, 8, 256), seed=40)
    qt = quantize_weight(_x((3, 256, 192), seed=41) * 0.1, "int8")
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    closed = jax.make_jaxpr(
        lambda a: quant_dot_experts(a, qt, plan, interpret=True))(x)
    assert _count_pallas_calls(closed.jaxpr) == 1
    assert _dots_outside_pallas(closed) == 0


@pytest.mark.parametrize("mode", MODES)
def test_quant_dot_experts_fused_matches_einsum_oracle(mode):
    """The 3-D rotate-once expert kernel is bitwise the einsum form for
    int8 (exact int32 accumulation) and allclose for fp8 (f32
    accumulation order differs between dot shapes)."""
    from repro.core.api import (QuantEpilogue, _experts_einsum_qw, plan_for,
                                quant_dot_experts)

    x = _x((2, 4, 6, 256), seed=42)
    qt = quantize_weight(_x((4, 256, 200), seed=43) * 0.1, mode)
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue(mode))
    got = np.asarray(quant_dot_experts(x, qt, plan), np.float32)
    want = np.asarray(_experts_einsum_qw(x, qt.q, qt.scale, plan, True),
                      np.float32)
    assert got.shape == (2, 4, 6, 200)
    if mode == "int8":
        assert (got == want).all()
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_quant_dot_experts_einsum_under_mesh():
    """Under an active mesh the expert site must stay on the
    GSPMD-shardable einsum form (a pallas_call would not partition)."""
    from repro.core.api import QuantEpilogue, plan_for, quant_dot_experts
    from repro.distributed import sharding as shd

    x = _x((1, 2, 4, 256), seed=44)
    qt = quantize_weight(_x((2, 256, 64), seed=45) * 0.1, "int8")
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    off_mesh = quant_dot_experts(x, qt, plan)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    key = ("pallas", "quant_dot_experts")
    obs = ("sharded_quant_dot", "experts_einsum_on_mesh")
    with shd.sharding_rules(mesh):
        before = registry.TRACE_COUNTS[key]
        obs_before = registry.TRACE_COUNTS[obs]
        on_mesh = quant_dot_experts(x, qt, plan)
        assert registry.TRACE_COUNTS[key] == before  # einsum path, no kernel
        # ... and the kernel-form bypass is observable
        assert registry.TRACE_COUNTS[obs] == obs_before + 1
    assert (np.asarray(on_mesh) == np.asarray(off_mesh)).all()


# ------------------------------------- streamed DMA-ring grid schedule
from repro.analysis import stream_events as _stream_events


def _streamed_jaxpr(d=640, bn=128, experts=False):
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import (pallas_quant_dot,
                                         pallas_quant_dot_experts)

    plan = plan_for(512, backend="pallas", epilogue=QuantEpilogue("int8"))
    sw = jnp.ones((1, d), jnp.float32)
    if experts:
        x = _x((1, 2, 8, 512))
        wq = jnp.zeros((2, 512, d), jnp.int8)
        swe = jnp.ones((2, 1, d), jnp.float32)
        return jax.make_jaxpr(
            lambda a, q, s: pallas_quant_dot_experts(
                a, q, s, plan, True, "streamed", bn))(x, wq, swe)
    x = _x((8, 512))
    wq = jnp.zeros((512, d), jnp.int8)
    return jax.make_jaxpr(
        lambda a, q, s: pallas_quant_dot(a, q, s, plan, True,
                                         "streamed", bn))(x, wq, sw)


@pytest.mark.parametrize("experts", [False, True], ids=["2d", "experts"])
def test_streamed_prefetch_starts_before_contraction(experts, monkeypatch):
    """Acceptance (structural): the streamed body kicks off the j+1
    copy-start BEFORE waiting on the j slot, and every DMA wait precedes
    the (single) top-level contraction -- the overlap window really
    exists in the kernel jaxpr rather than degenerate start->wait->dot
    per tile."""
    from repro.kernels.quant_dot import STREAM_INTERPRET_ENV

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    events = _stream_events(_kernel_jaxpr(_streamed_jaxpr(experts=experts)))
    assert events.count("dot") == 1, events     # the contraction only
    first_wait = events.index("wait")
    dot_at = events.index("dot")
    # warm-up (j==0) and prefetch (j+1) starts both precede the blocking
    # wait; the wait pair (weight + scale slots) precedes the dot
    assert events[:first_wait].count("start_cond") >= 2, events
    assert first_wait < dot_at and events[first_wait:dot_at].count(
        "wait") >= 2, events
    assert "start_cond" not in events[dot_at:], events


def test_streamed_keeps_rotate_once_transform_guard(monkeypatch):
    """Streaming replaces the weight fetch, not the schedule: the
    transform matmuls stay under the j == 0 cond (once per row block)
    and exactly one top-level dot_general contracts per tile."""
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import STREAM_INTERPRET_ENV

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    plan = plan_for(512, backend="pallas", epilogue=QuantEpilogue("int8"))
    top, in_cond = _dots_by_region(_kernel_jaxpr(_streamed_jaxpr()))
    assert top == 1, top
    assert in_cond == plan.num_passes, (in_cond, plan.num_passes)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_streamed_bitwise_vs_rotate_once(mode, dtype, monkeypatch):
    """Acceptance: streamed is bitwise rotate_once across all three quant
    modes x f32/bf16/fp16 -- d = 600 with block_n = 128 so the last tile
    is a padded tail (600 = 4*128 + 88) and the ring drains mid-tile."""
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import STREAM_INTERPRET_ENV, pallas_quant_dot

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    x = _x((23, 512), seed=50, dtype=dtype)
    wq, sw = quantize_weight(_x((512, 600), seed=51, dtype=dtype) * 0.05,
                             mode)
    plan = plan_for(512, dtype=dtype, backend="pallas",
                    epilogue=QuantEpilogue(mode))
    a = pallas_quant_dot(x, wq, sw, plan, True, "rotate_once", 128)
    b = pallas_quant_dot(x, wq, sw, plan, True, "streamed", 128)
    assert a.dtype == b.dtype == x.dtype
    assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


@pytest.mark.parametrize("mode", MODES)
def test_streamed_experts_bitwise_vs_rotate_once(mode, monkeypatch):
    """The 3-D (expert, rows, out-channels) ring resets slot parity at
    every new (expert, row-block) pair: multiple experts x multiple row
    blocks x a padded tail tile stay bitwise with the implicit fetch."""
    from repro.core.api import QuantEpilogue, plan_for
    from repro.kernels.quant_dot import (STREAM_INTERPRET_ENV,
                                         pallas_quant_dot_experts)

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    x = _x((2, 3, 6, 256), seed=52)
    qt = quantize_weight(_x((3, 256, 200), seed=53) * 0.1, mode)
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue(mode))
    a = pallas_quant_dot_experts(x, qt.q, qt.scale, plan, True,
                                 "rotate_once", 128)
    b = pallas_quant_dot_experts(x, qt.q, qt.scale, plan, True,
                                 "streamed", 128)
    assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_streamed_interpret_fallback_warns_once_and_counts(monkeypatch):
    """Without the force flag, interpret mode degrades streamed ->
    rotate_once: warn ONCE per process, tick
    TRACE_COUNTS[('quant_dot', 'stream_fallback')] every time, stay
    bitwise (mirrors the PR 5 _sharded_fallback pattern)."""
    import repro.kernels.quant_dot as qd

    monkeypatch.delenv(qd.STREAM_INTERPRET_ENV, raising=False)
    registry.WARN_ONCE_SEEN.discard(("quant_dot", "stream_fallback"))
    x = _x((4, 256), seed=54)
    wq, sw = quantize_weight(_x((256, 64), seed=55) * 0.1, "int8")
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    key = ("quant_dot", "stream_fallback")
    before = registry.TRACE_COUNTS[key]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        a = qd.pallas_quant_dot(x, wq, sw, plan, True, "streamed")
        b = qd.pallas_quant_dot(x, wq, sw, plan, True, "streamed")
    msgs = [r for r in rec if issubclass(r.category, RuntimeWarning)
            and "streamed" in str(r.message)]
    assert len(msgs) == 1, [str(r.message) for r in rec]
    assert registry.TRACE_COUNTS[key] == before + 2
    want = qd.pallas_quant_dot(x, wq, sw, plan, True, "rotate_once")
    assert (np.asarray(a) == np.asarray(want)).all()
    assert (np.asarray(b) == np.asarray(want)).all()
    # the force flag suppresses the fallback: streamed really runs
    monkeypatch.setenv(qd.STREAM_INTERPRET_ENV, "1")
    after = registry.TRACE_COUNTS[key]
    forced = qd.pallas_quant_dot(x, wq, sw, plan, True, "streamed")
    assert registry.TRACE_COUNTS[key] == after
    assert (np.asarray(forced) == np.asarray(want)).all()


def test_quant_dot_blocks_charges_streamed_ring():
    """Satellite: the block planner charges the second weight-tile slot +
    double scale slot + ring residency when sizing streamed blocks, and
    the returned BlockDecision exposes the schedule and the charged VMEM
    so benches can record them -- while staying a 2-tuple for legacy
    unpacking."""
    from repro.kernels.quant_dot import (_VMEM_BUDGET_BYTES, BlockDecision,
                                         quant_dot_blocks)

    args = (4096, 8192, 1 << 14, jnp.float32, jnp.float32, "int8")
    base = quant_dot_blocks(*args)
    streamed = quant_dot_blocks(*args, schedule="streamed")
    assert isinstance(base, BlockDecision) and isinstance(streamed,
                                                          BlockDecision)
    assert base.schedule == "rotate_once" and streamed.schedule == "streamed"
    # legacy consumers: tuple unpack and equality still work
    bm, bn = streamed
    assert (bm, bn) == (streamed.block_m, streamed.block_n)
    assert quant_dot_blocks(*args, block_m=8, block_n=256,
                            schedule="streamed") == (8, 256)
    # both decisions honor the budget; the ring narrows (or holds) bn
    # and, at equal tiles, charges strictly more VMEM
    assert base.vmem_bytes <= _VMEM_BUDGET_BYTES
    assert streamed.vmem_bytes <= _VMEM_BUDGET_BYTES
    assert streamed.block_n <= base.block_n
    pinned = dict(block_m=base.block_m, block_n=base.block_n)
    assert (quant_dot_blocks(*args, schedule="streamed",
                             **pinned).vmem_bytes >
            quant_dot_blocks(*args, **pinned).vmem_bytes)


# Phi-4-mini's w_down: every row block fetches and converts the whole
# 25 M-element fp8 weight, so the decision must give the rows the room
W_DOWN = dict(n=8192, d=3072)


@pytest.mark.parametrize("form", ["rotate_once", "streamed", "abft",
                                  "experts"])
def test_quant_dot_blocks_w_down_row_blocks(form, monkeypatch):
    """At Phi-4-mini's w_down (fp8_e4m3, bf16 io) a decode batch of 32
    rows is one row block and a 1024-row prefill bucket runs in blocks of
    at least 64 rows, within the VMEM budget; the traced call (the 3-D
    experts form included) counts exactly those row blocks."""
    from repro.kernels.quant_dot import (_VMEM_BUDGET_BYTES,
                                         STREAM_INTERPRET_ENV,
                                         pallas_quant_dot,
                                         pallas_quant_dot_experts,
                                         quant_dot_blocks)

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    n, d, bf16, f32, experts = (W_DOWN["n"], W_DOWN["d"], jnp.bfloat16,
                                jnp.float32, 2)
    schedule = "streamed" if form == "streamed" else "rotate_once"
    plan = plan_for(n, dtype=bf16, backend="pallas",
                    epilogue=QuantEpilogue("fp8_e4m3"))
    key = ("quant_dot", "row_blocks")
    for m in (32, 1024):
        dec = quant_dot_blocks(n, d, m, bf16, bf16, "fp8_e4m3",
                               schedule=schedule, abft=form == "abft")
        assert dec.vmem_bytes <= _VMEM_BUDGET_BYTES, dec
        if m == 32:
            assert dec.block_m == 32, dec
        else:
            assert dec.block_m >= 64 and m % dec.block_m == 0, dec
        # rows under a leading axis of 2: a shape no other test traces,
        # so the jitted wrapper traces here and the counter ticks
        before = registry.TRACE_COUNTS[key]
        if form == "experts":
            jax.make_jaxpr(lambda a, w, s: pallas_quant_dot_experts(
                a, w, s, plan, True, schedule))(
                jax.ShapeDtypeStruct((2, experts, m // 2, n), bf16),
                jax.ShapeDtypeStruct((experts, n, d), jnp.float8_e4m3fn),
                jax.ShapeDtypeStruct((experts, 1, d), f32))
            want = experts * (m // dec.block_m)
        else:
            check = (jax.ShapeDtypeStruct((1, n), f32) if form == "abft"
                     else None)
            jax.make_jaxpr(lambda a, w, s: pallas_quant_dot(
                a, w, s, plan, True, schedule, check=check))(
                jax.ShapeDtypeStruct((2, m // 2, n), bf16),
                jax.ShapeDtypeStruct((n, d), jnp.float8_e4m3fn),
                jax.ShapeDtypeStruct((1, d), f32))
            want = m // dec.block_m
        assert registry.TRACE_COUNTS[key] - before == want, (form, m, dec)


@pytest.mark.parametrize("schedule", ["rotate_once", "streamed"])
@pytest.mark.parametrize("mode", ["fp8_e4m3", "int8"])
def test_quant_dot_chunked_rows_and_k_slices_bitwise(mode, schedule,
                                                     monkeypatch):
    """A row block rotated in several chunks, with (fp8) its weight tile
    converted to bf16 in several K-slices, is bitwise the unfused XLA
    oracle and the 16-row block of the old tile decision -- and the
    traced call counts its row blocks, mp // bm."""
    from repro.kernels.quant_dot import (STREAM_INTERPRET_ENV,
                                         pallas_quant_dot, quant_dot_blocks,
                                         xla_quant_dot)

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    n, d, m, bf16 = 512, 256, 120, jnp.bfloat16
    x = _x((m, n), seed=60, dtype=bf16)
    wq, sw = quantize_weight(_x((n, d), seed=61) * 0.05, mode)
    plan = plan_for(n, dtype=bf16, backend="pallas",
                    epilogue=QuantEpilogue(mode))
    dec = quant_dot_blocks(n, d, m, bf16, bf16, mode, block_n=128,
                           schedule=schedule)
    assert dec.block_m >= 2 * dec.rotate_rows, dec
    if mode == "int8":
        assert dec.convert_rows == 0
    else:
        assert 0 < dec.convert_rows and n >= 2 * dec.convert_rows, dec
    key = ("quant_dot", "row_blocks")
    before = registry.TRACE_COUNTS[key]
    got = pallas_quant_dot(x, wq, sw, plan, True, schedule, 128)
    mp = -(-m // dec.block_m) * dec.block_m
    assert registry.TRACE_COUNTS[key] - before == mp // dec.block_m
    pinned = plan_for(n, dtype=bf16, backend="pallas",
                      epilogue=QuantEpilogue(mode), block_m=16)
    old = pallas_quant_dot(x, wq, sw, pinned, True, schedule, 128)
    want = xla_quant_dot(x, wq, sw, plan, True)
    got, old, want = (np.asarray(a, np.float32) for a in (got, old, want))
    assert (got == want).all()
    assert (got == old).all()


def test_quant_dot_schedule_through_public_api(monkeypatch):
    """The schedule kwarg rides quant_dot / quant_dot_experts /
    QuantDotSpec end to end (custom_vjp nondiff plumbing) and composes
    with an explicit plan -- it is dispatch-level, not plan config."""
    from repro.core.api import QuantDotSpec, quant_dot_experts
    from repro.kernels.quant_dot import STREAM_INTERPRET_ENV

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    x = _x((9, 256), seed=56)
    w = _x((256, 320), seed=57) * 0.05
    qt = quantize_weight(w, "int8")
    want = quant_dot(x, qt, mode="int8", backend="pallas")
    got = quant_dot(x, qt, mode="int8", backend="pallas",
                    schedule="streamed")
    assert (np.asarray(got) == np.asarray(want)).all()
    # explicit plan + schedule does NOT trip the plan/kwargs guard
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    assert (np.asarray(quant_dot(x, qt, plan, schedule="streamed"))
            == np.asarray(want)).all()
    # spec-bound site + validation
    spec = QuantDotSpec(n=256, mode="int8", backend="pallas",
                        schedule="streamed")
    assert (np.asarray(spec(x, qt)) == np.asarray(want)).all()
    with pytest.raises(ValueError, match="schedule"):
        QuantDotSpec(n=256, schedule="bogus")
    # STE gradients are schedule-invariant (nondiff argnum plumbing)
    gx = jax.grad(lambda a: jnp.sum(
        quant_dot(a, w, mode="int8", backend="pallas",
                  schedule="streamed") ** 2))(x)
    gx0 = jax.grad(lambda a: jnp.sum(
        quant_dot(a, w, mode="int8", backend="pallas") ** 2))(x)
    assert (np.asarray(gx) == np.asarray(gx0)).all()
    # experts: spec + function form
    xe = _x((1, 2, 4, 256), seed=58)
    qte = quantize_weight(_x((2, 256, 128), seed=59) * 0.1, "int8")
    eplan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    ewant = quant_dot_experts(xe, qte, eplan)
    egot = quant_dot_experts(xe, qte, eplan, schedule="streamed")
    assert (np.asarray(egot) == np.asarray(ewant)).all()


def test_streamed_env_var_resolution(monkeypatch):
    """REPRO_QUANT_DOT_SCHEDULE=streamed flips the default (the tier-1 CI
    streamed leg); an explicit schedule argument beats the env."""
    from repro.kernels.quant_dot import (SCHEDULE_ENV_VAR,
                                         STREAM_INTERPRET_ENV,
                                         pallas_quant_dot)

    monkeypatch.setenv(STREAM_INTERPRET_ENV, "1")
    x = _x((4, 256), seed=60)
    wq, sw = quantize_weight(_x((256, 64), seed=61) * 0.1, "int8")
    plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"))
    want = pallas_quant_dot(x, wq, sw, plan, True, "rotate_once")
    monkeypatch.setenv(SCHEDULE_ENV_VAR, "streamed")
    got = pallas_quant_dot(x, wq, sw, plan, True)       # env default
    assert (np.asarray(got) == np.asarray(want)).all()
    got2 = pallas_quant_dot(x, wq, sw, plan, True, "revisit")  # arg wins
    assert (np.asarray(got2) == np.asarray(want)).all()


# ---------------------------------------------------------------- shims
def test_deprecation_shims_warn_once():
    from repro.kernels import fused_quant, ops

    for mod, call in (
        (ops, lambda: ops.hadamard(_x((2, 128)))),
        (fused_quant,
         lambda: fused_quant.fused_hadamard_quantize(_x((2, 128)))),
    ):
        registry.WARN_ONCE_SEEN.discard(mod.WARN_KEY)
        before = registry.TRACE_COUNTS[mod.WARN_KEY]
        with pytest.warns(DeprecationWarning):
            call()
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # second call must stay silent
            call()
        # the shared warn_once util keeps counting after going quiet
        assert registry.TRACE_COUNTS[mod.WARN_KEY] == before + 2
