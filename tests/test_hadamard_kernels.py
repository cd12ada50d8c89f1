"""Per-kernel correctness: hadacore (Pallas, interpret) and the factored
XLA path against the pure-jnp FWHT oracle and explicit Hadamard matmul,
swept over shapes and dtypes (the paper's unit-test methodology)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hadamard import (
    base_matrices_np,
    grouped_hadamard,
    hadamard_transform,
    pack_pass_mats,
    unpack_pass_mats,
)
from repro.kernels.fused_quant import fused_hadamard_quantize, ref_fused
from repro.kernels.hadacore import hadacore
from repro.kernels.ops import hadamard
from repro.kernels.ref import fwht, hadamard_matrix

SIZES = [2, 8, 16, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768]


@pytest.mark.parametrize("n", SIZES)
def test_hadacore_matches_explicit_matmul(n):
    rng = np.random.default_rng(n)
    rows = 3 if n >= 8192 else 9
    x = rng.standard_normal((rows, n)).astype(np.float32)
    want = x @ hadamard_matrix(n)
    got = np.asarray(hadacore(jnp.asarray(x), scale=None))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4 * math.sqrt(n))


@pytest.mark.parametrize("n", [128, 512, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_hadacore_dtypes(n, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((17, n)), dtype=dtype)
    got = hadacore(x, scale="ortho").astype(jnp.float32)
    want = fwht(x.astype(jnp.float32), scale=1.0 / math.sqrt(n))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)
    assert hadacore(x).dtype == dtype


@pytest.mark.parametrize("n", SIZES)
def test_xla_factored_path(n):
    rng = np.random.default_rng(n)
    x = rng.standard_normal((5, n)).astype(np.float32)
    got = np.asarray(hadamard_transform(jnp.asarray(x), scale=None))
    want = np.asarray(fwht(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("batch_shape", [(1,), (4, 3), (2, 2, 5)])
def test_leading_dims(batch_shape):
    rng = np.random.default_rng(1)
    x = rng.standard_normal(batch_shape + (256,)).astype(np.float32)
    got = np.asarray(hadacore(jnp.asarray(x)))
    want = np.asarray(fwht(jnp.asarray(x), scale=1 / 16.0))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)


def test_in_place_aliasing():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, 1024)), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(hadacore(x, in_place=True)),
                               np.asarray(hadacore(x)), rtol=0, atol=0)


def test_block_m_variants():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((70, 512)), dtype=jnp.float32)  # pad path
    want = np.asarray(hadacore(x))
    for bm in (8, 16, 64):
        np.testing.assert_allclose(np.asarray(hadacore(x, block_m=bm)), want,
                                   rtol=1e-6, atol=1e-6)


def test_kernel_size_cap():
    with pytest.raises(ValueError):
        hadacore(jnp.zeros((2, 65536)))
    # ...but the factored path covers it
    y = hadamard_transform(jnp.zeros((2, 65536)))
    assert y.shape == (2, 65536)


@pytest.mark.parametrize("n", [2, 64, 128, 256, 2048])
def test_pass_matrices_compose_to_hadamard(n):
    """H_n = H_a (x) H_b with b = min(n, 128), and the packed kernel
    operand unpacks to the same matrices."""
    mats = base_matrices_np(n, None)
    assert [m.shape[0] for m in mats] == [min(n, 128)] + (
        [n // 128] if n > 128 else [])
    full = mats[0] if len(mats) == 1 else np.kron(mats[1], mats[0])
    np.testing.assert_array_equal(full, hadamard_matrix(n))
    for m, u in zip(mats, unpack_pass_mats(pack_pass_mats(mats), n),
                    strict=True):
        np.testing.assert_array_equal(u, m)
    with pytest.raises(ValueError):
        base_matrices_np(3 * n, None)


# --------------------------------------------------------------- properties
@settings(deadline=None, max_examples=25)
@given(logn=st.integers(1, 12), seed=st.integers(0, 2**31 - 1))
def test_property_self_inverse(logn, seed):
    """H orthonormal and symmetric => had(had(x)) == x."""
    n = 2 ** logn
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((4, n)), dtype=jnp.float32)
    y = hadamard(hadamard(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-4, atol=1e-4)


@settings(deadline=None, max_examples=25)
@given(logn=st.integers(1, 12), seed=st.integers(0, 2**31 - 1))
def test_property_norm_preservation(logn, seed):
    """Orthonormal transform preserves L2 norms (it is a rotation)."""
    n = 2 ** logn
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((4, n)), dtype=jnp.float32)
    y = hadamard(x)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-4)


@settings(deadline=None, max_examples=20)
@given(logn=st.integers(1, 10), seed=st.integers(0, 2**31 - 1),
       a=st.floats(-3, 3), b=st.floats(-3, 3))
def test_property_linearity(logn, seed, a, b):
    n = 2 ** logn
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, n)), dtype=jnp.float32)
    z = jnp.asarray(rng.standard_normal((2, n)), dtype=jnp.float32)
    lhs = hadamard(a * x + b * z)
    rhs = a * hadamard(x) + b * hadamard(z)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-3, atol=1e-3)


@settings(deadline=None, max_examples=15)
@given(g=st.integers(1, 9), logp=st.integers(1, 8), seed=st.integers(0, 1000))
def test_property_grouped_orthogonal(g, logp, seed):
    """Grouped transform (non-pow2 dims) is still orthogonal."""
    p = 2 ** logp
    n = g * p
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((3, n)), dtype=jnp.float32)
    y = grouped_hadamard(x, group=p)
    z = grouped_hadamard(y, group=p)
    np.testing.assert_allclose(np.asarray(z), np.asarray(x), rtol=1e-4, atol=1e-4)


def test_gradient_is_self_adjoint():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 512)), dtype=jnp.float32)
    g = jax.grad(lambda a: jnp.sum(hadamard(a) ** 2))(x)
    # d/dx ||xH||^2 = 2 x H H^T = 2x for orthonormal H
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- fused kernel
@pytest.mark.parametrize("n", [128, 512, 2048, 4096])
def test_fused_hadamard_quantize(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal((13, n)), dtype=jnp.float32)
    q, s = fused_hadamard_quantize(x)
    qr, sr = ref_fused(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    # int8 grids may differ by 1 ulp at rounding boundaries
    assert np.mean(np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))) < 0.01
    # dequantized result approximates the rotation
    deq = np.asarray(q, np.float32) * np.asarray(s)
    want = np.asarray(fwht(x, scale=1.0 / math.sqrt(n)))
    np.testing.assert_allclose(deq, want, atol=np.abs(want).max() / 100)
