"""The plan-based unified Hadamard API (DESIGN.md section 5): plan
caching, backend registry selection, composable quantize epilogues
against the extended oracle, custom_vjp through fused and unfused paths,
and the end-to-end claim -- a quantized+rotated model forward routes the
down-projection input through ONE fused pallas_call."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import (
    HadamardPlan,
    QuantEpilogue,
    hadamard,
    make_plan,
    plan_for,
)
from repro.core.hadamard import grouped_hadamard, hadamard_transform
from repro.core.quant import QuantConfig, quantize
from repro.core.rotations import online_hadamard_quantize
from repro.kernels import registry
from repro.kernels.fused_quant import fused_hadamard_quantize, ref_fused
from repro.kernels.ref import fwht


def _x(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# ------------------------------------------------------------- plan cache
def test_plan_cache_returns_same_object():
    p1 = plan_for(1024, backend="pallas")
    p2 = plan_for(1024, backend="pallas")
    assert p1 is p2
    assert make_plan is plan_for or make_plan(1024, backend="pallas") is p1


def test_repeated_shapes_do_not_recompile(monkeypatch):
    # pin auto-selection (the CI matrix runs the suite under a backend
    # env override; this test is about the plan/jit caches, not dispatch)
    monkeypatch.delenv(registry.BACKEND_ENV_VAR, raising=False)
    x = _x((16, 256))
    hadamard(x)  # warm: plan + jit cache
    key = ("pallas", "transform")
    before = registry.TRACE_COUNTS[key]
    for seed in range(3):
        hadamard(_x((16, 256), seed=seed))
    assert registry.TRACE_COUNTS[key] == before  # same plan, no retrace
    hadamard(_x((16, 512)))  # different shape -> exactly one new trace
    assert registry.TRACE_COUNTS[("pallas", "transform")] == before + 1


def test_plan_precomputes_factorization():
    p = plan_for(32768, backend="pallas")
    # H_256 (x) H_128: a 128-lane pass and a 256-row pass, packed into
    # one (2, 256, 256) operand
    assert p.num_passes == 2 and p.mats.shape == (2, 256, 256)
    small = plan_for(64, backend="pallas")
    assert small.num_passes == 1 and small.mats.shape == (1, 64, 64)
    grouped = plan_for(14336)  # 7 * 2048
    assert grouped.grouped and grouped.p == 2048
    assert isinstance(grouped, HadamardPlan)


# ----------------------------------------------------------- registry
def test_backend_auto_selection_by_size(monkeypatch):
    monkeypatch.delenv(registry.BACKEND_ENV_VAR, raising=False)
    assert plan_for(2048).backend == "pallas"  # kernel cap covers it
    assert plan_for(65536).backend == "xla"    # above 2^15: factored path


def test_backend_env_override(monkeypatch):
    monkeypatch.setenv(registry.BACKEND_ENV_VAR, "xla")
    plan = plan_for(4096)
    assert plan.backend == "xla"
    # explicit argument beats the env var
    assert plan_for(4096, backend="pallas").backend == "pallas"
    monkeypatch.setenv(registry.BACKEND_ENV_VAR, "nope")
    with pytest.raises(ValueError):
        plan_for(8192)


def test_unknown_backend_raises():
    with pytest.raises(ValueError):
        plan_for(256, backend="cuda")


def test_ref_backend_matches_oracle_but_never_auto():
    x = _x((4, 256))
    y = hadamard(x, backend="ref")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(fwht(x, 1 / 16.0)), rtol=1e-6)
    assert "ref" not in {plan_for(n).backend for n in (64, 1024, 65536)}


# ----------------------------------------------------------- validation
def test_scale_typo_raises_everywhere():
    x = _x((4, 128))
    for fn in (lambda: hadamard(x, scale="orth"),
               lambda: hadamard_transform(x, scale="orth"),
               lambda: plan_for(128, scale="orth")):
        with pytest.raises(ValueError):
            fn()
    # None stays explicitly accepted (the +-1 transform)
    np.testing.assert_allclose(np.asarray(hadamard(x, scale=None)),
                               np.asarray(fwht(x)), rtol=2e-5, atol=1e-3)


def test_unknown_epilogue_mode_raises():
    with pytest.raises(ValueError):
        QuantEpilogue("int4")


def test_plan_shape_mismatch_raises():
    plan = plan_for(256)
    with pytest.raises(ValueError):
        hadamard(_x((4, 128)), plan)
    with pytest.raises(ValueError):
        hadamard(_x((4, 256), dtype=jnp.bfloat16), plan)


def test_plan_with_conflicting_kwargs_raises():
    plan = plan_for(256)
    x = _x((4, 256))
    with pytest.raises(ValueError, match="explicit plan"):
        hadamard(x, plan, epilogue=QuantEpilogue("int8"))
    with pytest.raises(ValueError, match="explicit plan"):
        hadamard(x, plan, scale=None)


def test_legacy_op_rejects_non_pow2():
    from repro.kernels.ops import hadamard as old_hadamard

    with pytest.raises(ValueError):  # grouped transform is plan-API opt-in
        old_hadamard(_x((4, 24)))


# ----------------------------------------------------------- epilogues
def test_int8_epilogue_bitwise_matches_legacy_shim():
    x = _x((13, 2048), seed=3)
    q, s = hadamard(x, epilogue=QuantEpilogue("int8"), backend="pallas")
    q_old, s_old = fused_hadamard_quantize(x)
    assert q.dtype == jnp.int8
    assert (np.asarray(q) == np.asarray(q_old)).all()
    assert (np.asarray(s) == np.asarray(s_old)).all()


@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3", "fp8_e5m2"])
@pytest.mark.parametrize("n", [128, 1024])
def test_fused_epilogues_match_ref_oracle(mode, n):
    x = _x((9, n), seed=n)
    q, s = hadamard(x, epilogue=QuantEpilogue(mode), backend="pallas")
    qr, sr = ref_fused(x, mode=mode)
    assert q.dtype == qr.dtype
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    # grids may differ by 1 ulp at rounding boundaries
    dq = np.abs(np.asarray(q, np.float32) - np.asarray(qr, np.float32))
    denom = max(np.abs(np.asarray(qr, np.float32)).max(), 1.0)
    assert np.mean(dq) / denom < 0.01
    # dequantized result approximates the rotation; tolerance tracks the
    # grid's relative step (e5m2: 2 mantissa bits -> ~12.5% per-value)
    rel_tol = {"int8": 1 / 50, "fp8_e4m3": 1 / 20, "fp8_e5m2": 1 / 7}[mode]
    deq = np.asarray(q, np.float32) * np.asarray(s)
    want = np.asarray(fwht(x, scale=1.0 / math.sqrt(n)))
    assert np.abs(deq - want).max() < np.abs(want).max() * rel_tol


def test_dequant_epilogue_matches_two_step_fake_quant():
    x = _x((8, 512), seed=5)
    for mode in ("int8", "fp8_e4m3", "fp8_e5m2"):
        fused = hadamard(
            x, epilogue=QuantEpilogue(mode, dequant=True), backend="pallas")
        two = quantize(hadamard_transform(x), mode, axis=-1)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(two),
                                   rtol=1e-4, atol=1e-4)


def test_grouped_epilogue_keeps_per_full_token_scales():
    x = _x((6, 1536), seed=7)  # 1536 = 3 * 512: grouped transform
    q, s = hadamard(x, epilogue=QuantEpilogue("int8"))
    assert q.shape == x.shape and s.shape == (6, 1)
    want_q, want_s = (
        np.asarray(t) for t in _quant_ref(grouped_hadamard(x)))
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-5)
    assert np.mean(np.asarray(q, np.int32) != want_q) < 0.01


def _quant_ref(y):
    s = jnp.maximum(jnp.max(jnp.abs(y), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(y / s), -127, 127).astype(jnp.int8)
    return q, s


def test_per_tensor_epilogue():
    x = _x((4, 256), seed=9)
    q, s = hadamard(x, epilogue=QuantEpilogue("int8", per_token=False))
    y = np.asarray(hadamard_transform(x), np.float32)
    np.testing.assert_allclose(float(np.ravel(np.asarray(s))[0]),
                               max(np.abs(y).max(), 1e-8) / 127.0, rtol=1e-5)


# ------------------------------------------------------------- autodiff
def test_transform_vjp_self_adjoint():
    x = _x((4, 512), seed=11)
    g = jax.grad(lambda a: jnp.sum(hadamard(a) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3", "fp8_e5m2"])
def test_fused_dequant_vjp_is_straight_through(mode):
    x = _x((4, 256), seed=13)
    w = _x((4, 256), seed=14)
    epi = QuantEpilogue(mode, dequant=True)
    g = jax.grad(lambda a: jnp.sum(hadamard(a, epilogue=epi) * w))(x)
    # STE: quantize behaves as identity in the pullback, so the gradient
    # is exactly the (self-adjoint) rotation of w.
    np.testing.assert_allclose(np.asarray(g), np.asarray(hadamard(w)),
                               rtol=1e-5, atol=1e-5)


def test_fused_qs_vjp_scale_branch_is_zero():
    # The (q, scales) form quantizes to an integer grid: its quantized
    # branch is non-differentiable (use dequant=True for training); the
    # scale branch is defined as a statistic with zero pullback.
    x = _x((4, 256), seed=15)
    g = jax.grad(
        lambda a: jnp.sum(hadamard(a, epilogue=QuantEpilogue("int8"))[1]))(x)
    assert g.shape == x.shape
    assert float(jnp.abs(g).max()) == 0.0


def test_model_helper_vjp_flows():
    cfg = QuantConfig(mode="int8", rotate="hadamard", backend="pallas")
    x = _x((2, 3, 512), seed=17)
    g = jax.grad(lambda a: jnp.sum(online_hadamard_quantize(a, cfg) ** 2))(x)
    assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0


# ----------------------------------------------------- end-to-end model
# shared with the lint rules: tests and CI assert one implementation
from repro.analysis import count_pallas_calls as _count_pallas_calls


def test_model_down_proj_routes_through_single_fused_kernel():
    """QuantConfig(mode='int8', rotate='hadamard', backend='pallas') must
    rotate + quantize the down-projection input in ONE pallas_call, and
    match the unfused xla-backend forward."""
    from repro.configs import get_config
    from repro.models.mlp import apply_mlp, init_mlp

    cfg = get_config("llama3_8b").scaled_down(
        d_ff=512, dtype="float32").with_quant(
        QuantConfig(mode="int8", rotate="hadamard", backend="pallas"))
    p = init_mlp(jax.random.PRNGKey(0), cfg)
    x = _x((2, 4, cfg.d_model), seed=19)

    jaxpr = jax.make_jaxpr(lambda a: apply_mlp(cfg, p, a))(x)
    assert _count_pallas_calls(jaxpr.jaxpr) == 1

    y_fused = apply_mlp(cfg, p, x)
    cfg_xla = cfg.with_quant(
        QuantConfig(mode="int8", rotate="hadamard", backend="xla"))
    y_two = apply_mlp(cfg_xla, p, x)
    scale = float(jnp.abs(y_two).max())
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_two),
                               atol=2e-3 * scale, rtol=1e-3)


def test_rotation_only_model_path_has_no_quantize_fallback():
    # rotate without quantization still goes through the plan API
    from repro.configs import get_config
    from repro.models.mlp import apply_mlp, init_mlp

    cfg = get_config("llama3_8b").scaled_down(d_ff=512, dtype="float32")
    cfg = cfg.with_quant(QuantConfig(rotate="hadamard", backend="pallas"))
    p = init_mlp(jax.random.PRNGKey(1), cfg)
    x = _x((2, 4, cfg.d_model), seed=21)
    jaxpr = jax.make_jaxpr(lambda a: apply_mlp(cfg, p, a))(x)
    assert _count_pallas_calls(jaxpr.jaxpr) == 1


# --------------------------------------------------------------- shims
def test_legacy_entry_points_importable_and_consistent():
    from repro.kernels.fused_quant import fused_hadamard_quantize as fhq
    from repro.kernels.ops import hadamard as old_hadamard

    x = _x((4, 1024), seed=23)
    np.testing.assert_allclose(np.asarray(old_hadamard(old_hadamard(x))),
                               np.asarray(x), rtol=1e-4, atol=1e-4)
    q, s = fhq(x)
    qr, sr = ref_fused(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    with pytest.raises(ValueError):
        fhq(_x((2, 96)))  # non-power-of-2 still rejected by the shim
