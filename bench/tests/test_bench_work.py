"""The operation and byte counts against hand counts."""
import math

import pytest

from bench.metrics_util import call_work
from bench.work import counts

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
        "vocab_size": 10, "hidden_act": "silu"}

# instructions as a TPU trace names them (Phi-4-mini's w_down at 32
# decode rows; StarCoder2's grouped d_ff at the 2048 prefill bucket)
QUANT_DOT = (
    "%_pallas_quant_dot.5 = bf16[32,3072]{1,0:T(8,128)(2,1)S(1)} "
    "custom-call(bf16[32,8192]{1,0:T(8,128)(2,1)S(1)} %fusion.163, "
    "bf16[2,128,128]{2,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.729, "
    "f8e4m3fn[8192,3072]{1,0:T(8,128)(4,1)S(1)} %dynamic-slice_fusion.10, "
    "f32[1,3072]{1,0:T(1,128)S(1)} %dynamic-slice_fusion.11), "
    "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
    "{bf16[32,8192]{1,0}, bf16[2,128,128]{2,1,0}, f8e4m3fn[8192,3072]{1,0}, "
    "f32[1,3072]{1,0}}")
TRANSFORM = (
    "%_pallas_transform.1 = bf16[6144,8192]{1,0:T(8,128)(2,1)} "
    "custom-call(bf16[6144,8192]{1,0:T(8,128)(2,1)} %bitcast.7, "
    "bf16[2,128,128]{2,1,0:T(8,128)(2,1)} %constant.9), "
    "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("used", [32, 20])
def test_quant_dot_hand_count(used):
    # 32 decode rows, of which ``used`` slots hold a request: the rows
    # in and out count the used slots, the weight and scales whole
    ops, byt = call_work("quant_dot", QUANT_DOT, used, 32)
    assert ops == 2 * used * 8192 * 3072 + used * 8192 * 13
    assert byt == (used * 8192 * 2 + 2 * 128 * 128 * 2 + 8192 * 3072
                   + 4 * 3072 + used * 3072 * 2)


@pytest.mark.parametrize("prompt", [2048, 1000])
def test_transform_hand_count(prompt):
    # a prompt in the 2048 bucket, each token as 3 groups of 8192, 13
    # butterfly stages of additions; the bucket's padding counts nothing
    ops, byt = call_work("hadamard", TRANSFORM, prompt, 2048)
    assert ops == 3 * prompt * 8192 * 13
    assert byt == 3 * prompt * 8192 * 2 * 2 + 2 * 128 * 128 * 2


def test_work_does_not_depend_on_the_padding():
    # the same prompt, handed to the kernel in a bucket half as large
    half = TRANSFORM.replace("6144,", "3072,")
    assert call_work("hadamard", half, 1000, 1024) \
        == call_work("hadamard", TRANSFORM, 1000, 2048)


def test_decode_step_hand_count():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    mats = 64 + 32 + 32 + 64 + 3 * 128
    assert mats == 576
    depth = 5
    attn = 4 * 8 * (depth + 1)             # QK^T and PV over 6 rows
    want = 3 * (2 * mats + attn) + 2 * 8 * 10
    assert counts.decode_flops(TINY, depth) == want


def test_prefill_counts_the_causal_triangle():
    n = 7
    attn = 4 * 8 * n * (n + 1) / 2
    want = 3 * (2 * 576 * n + attn) + 2 * 8 * 10
    assert counts.prefill_flops(TINY, n) == want
    gelu = dict(TINY, hidden_act="gelu_pytorch_tanh")   # two MLP matrices
    assert counts.prefill_flops(TINY, n) - counts.prefill_flops(gelu, n) \
        == 3 * 2 * 128 * n


@pytest.mark.parametrize("ops,byt,bound", [(197e12, 1.0, 1.0),
                                           (1.0, 819e9 * 2, 2.0)])
def test_roofline_time_is_the_larger_bound(ops, byt, bound):
    assert math.isclose(counts.roofline_time(ops, byt, 197e12, 819e9),
                        bound)
