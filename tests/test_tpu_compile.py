"""The main path's Pallas kernels at Phi-4-mini's widths, compiled by
Mosaic for a described TPU v5e (no chip needed): what the chip's
compiler refuses fails here, at no chip time. Plus CPU checks of the
platform rules that keep kernels from quietly leaving the chip.

The topology is described only inside the module fixture -- never at
import -- so that one test worker loads the TPU compiler and every
worker collects the same tests."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.api import QuantEpilogue, _qd_fusable, hadamard, plan_for, quant_dot
from repro.jaxapi import fp8_operand_dtype, interpret_mode
from repro.kernels import registry

D_FF, D_MODEL, HEAD_DIM = 8192, 3072, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# every pass structure: n <= 128 (one lane pass), a = n/128 of 2, 4, 16,
# 64 and 256 sublane rows (the last at the kernel cap)
OTHER_SIZES = [256, 512, 2048, 32768]


@pytest.mark.parametrize("n", [HEAD_DIM, D_FF] + OTHER_SIZES)
def test_transform_compiles_for_v5e(one_chip, n):
    x = jax.ShapeDtypeStruct((4096, n), BF16, sharding=one_chip)
    plan = plan_for(n, dtype=BF16, backend="pallas")
    _compile(lambda a: hadamard(a, plan, interpret=False), x)


@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3"])
def test_qk_rotate_quantize_compiles_for_v5e(one_chip, mode):
    # the attention QK site: (slots, 1, heads, head_dim) decode rows
    x = jax.ShapeDtypeStruct((8, 1, 24, HEAD_DIM), BF16, sharding=one_chip)
    plan = plan_for(HEAD_DIM, dtype=BF16, backend="pallas",
                    epilogue=QuantEpilogue(mode, dequant=True))
    _compile(lambda a: hadamard(a, plan, interpret=False), x)


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("schedule", ["rotate_once", "streamed"])
@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3"])
def test_quant_dot_compiles_for_v5e(one_chip, mode, schedule, m):
    # Phi-4-mini's w_down: decode (m=8) and prefill (m=2048) rows; fp8
    # prefill tiles overflowed the 16 MiB scoped VMEM until the rotation's
    # f32 temporaries were charged
    _compile_quant_dot(one_chip, D_FF, mode, schedule, m)


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("schedule", ["rotate_once", "streamed"])
@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("n", OTHER_SIZES)
def test_quant_dot_compiles_for_v5e_at_other_sizes(one_chip, n, mode,
                                                   schedule, m):
    # fp8 prefill at n=512 and 2048 overflowed the scoped VMEM until the
    # fp8 tile's f32 conversion and the lane-padded rotation temporaries
    # of small n were charged; fp8 at n=32768 takes the unfused path
    _compile_quant_dot(one_chip, n, mode, schedule, m)


def _compile_quant_dot(sharding, n, mode, schedule, m):
    x = jax.ShapeDtypeStruct((m, n), BF16, sharding=sharding)
    wq = jax.ShapeDtypeStruct((n, D_MODEL), registry.QSPECS[mode][1],
                              sharding=sharding)
    sw = jax.ShapeDtypeStruct((1, D_MODEL), jnp.float32, sharding=sharding)
    plan = plan_for(n, dtype=BF16, backend="pallas",
                    epilogue=QuantEpilogue(mode))
    _compile(lambda a, w, s: quant_dot(a, (w, s), plan, interpret=False,
                                       schedule=schedule), x, wq, sw)


# --------------------------------------------------- platform rules (CPU)
def test_interpret_mode_by_platform():
    assert interpret_mode("cpu") is True
    assert interpret_mode("tpu") is False
    assert interpret_mode() is (jax.default_backend() == "cpu")
    for other in ("gpu", "cuda", "metal"):
        with pytest.raises(RuntimeError, match="neither"):
            interpret_mode(other)


def test_fp8_operand_dtype_by_platform():
    assert fp8_operand_dtype("tpu") == jnp.bfloat16
    assert fp8_operand_dtype("cpu") == jnp.float32


def test_requested_backend_that_cannot_run_warns_once_and_counts():
    key = ("backend_fallback", "pallas")
    registry.WARN_ONCE_SEEN.discard(key)
    before = registry.TRACE_COUNTS[key]
    with pytest.warns(RuntimeWarning, match="cannot run"):
        assert registry.select_backend(2 * registry.MAX_KERNEL_SIZE,
                                       "pallas") == "xla"
    assert registry.select_backend(2 * registry.MAX_KERNEL_SIZE,
                                   "pallas") == "xla"     # quiet, counted
    assert registry.TRACE_COUNTS[key] == before + 2
    assert registry.select_backend(256, "pallas") == "pallas"
    assert registry.TRACE_COUNTS[key] == before + 2


def test_quant_dot_over_vmem_budget_warns_once_and_counts():
    key = ("quant_dot", "vmem_unfused")
    registry.WARN_ONCE_SEEN.discard(key)
    before = registry.TRACE_COUNTS[key]
    # fp8: a (32768, 128) weight tile charges two 4-MiB storage slots
    # and an 8-MiB bf16 operand
    big = plan_for(32768, backend="pallas",
                   epilogue=QuantEpilogue("fp8_e4m3"))
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        assert not _qd_fusable(big)
    assert registry.TRACE_COUNTS[key] == before + 1
    assert _qd_fusable(plan_for(D_FF, backend="pallas",
                                epilogue=QuantEpilogue("fp8_e4m3")))
    assert registry.TRACE_COUNTS[key] == before + 1
