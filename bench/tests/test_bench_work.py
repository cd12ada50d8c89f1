"""The operation and byte counts against hand counts."""
import math

import pytest

from bench.metrics_util import call_work
from bench.trace import call_shapes, shape_bytes
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


def _rows(instruction, whole, part):
    return instruction.replace(f"[{whole},", f"[{part},")


# (kernel, the whole call, its rows, a quarter's rows as the kernel pads
# them, the step's tokens); the transform pads 1536 rows to its row block
SPLITS = [("quant_dot", QUANT_DOT, 32, 8, 32),
          ("hadamard", TRANSFORM, 6144, 1536, 2048),
          ("hadamard", TRANSFORM, 6144, 1600, 2048)]


@pytest.mark.parametrize("kernel,whole,rows,quarter,built_for", SPLITS)
@pytest.mark.parametrize("share", [1.0, 0.5, 1 / 32])
def test_quarter_row_calls_add_up_to_the_whole_call(kernel, whole, rows,
                                                     quarter, built_for,
                                                     share):
    # a mesh of four splits the call's rows four ways: the quarters'
    # operations and row bytes are the whole call's; each quarter reads
    # the weight, scales and pass matrices whole on its own chip
    tokens = built_for * share
    ops, byt = call_work(kernel, whole, tokens, built_for)
    part = _rows(whole, rows, quarter)
    q_ops, q_byt = call_work(kernel, part, tokens, built_for, 4)
    assert 4 * q_ops == pytest.approx(ops, rel=1e-12)
    _, operands = call_shapes(whole)
    shared = shape_bytes(operands[1:])
    assert 4 * (q_byt - shared) == pytest.approx(byt - shared, rel=1e-12)


def test_fewer_tokens_than_shards_count_a_fraction():
    part = _rows(QUANT_DOT, 32, 8)
    ops, byt = call_work("quant_dot", part, 1, 32, 4)
    assert ops == pytest.approx(2 * 0.25 * 8192 * 3072 + 0.25 * 8192 * 13)
    assert byt > 8192 * 3072


@pytest.mark.parametrize("metric", ["decode.mfu_pct", "prefill.mfu_pct"])
def test_mfu_counts_every_chip(metric):
    from types import SimpleNamespace

    from bench import harness, spec

    log = SimpleNamespace(open=0.0, close=10.0, admits=[(1.0, 1.5, 7)],
                          decodes=[(2.0, 2.1, [5, 9]), (3.0, 3.2, [6])])
    config = dict(TINY, program={"matmul_dtype": "bf16"})
    peaks = {"flops": {"bf16": 1e6}}

    def read(chips):
        cell = SimpleNamespace(config=config, traffic={}, chips=chips)
        run = harness.Run(cell, log, 1.0, None, peaks, 10.0)
        return spec.load_reader(metric)(run)

    assert read(4) == pytest.approx(read(1) / 4, rel=1e-12)
    assert read(1) > 0
