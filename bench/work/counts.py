"""Operations and bytes, counted from shapes and dtypes.

A step's useful work counts what the model needs, whatever implements
it: matmuls over the real (unpadded) prompt tokens, causal attention
(the lower triangle, diagonal included), decode attention over each
slot's real depth, and the logits only where a token is chosen. A
multiply-add is two operations. A kernel's work counts the rows of its
call that hold real tokens (``metrics_util.call_work``), so a program
that pads less reads a higher share of the roofline; its bytes
(``trace.shape_bytes``) are each operand read once and each result
written once.
"""
from __future__ import annotations

import math

def _layer_matmul_params(m: dict) -> int:
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    hq, hk = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    mlp = (3 if m["hidden_act"] == "silu" else 2) * d * f
    return d * hq + 2 * d * hk + hq * d + mlp


def _attn_width(m: dict) -> int:
    hd = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return m["num_attention_heads"] * hd


def prefill_flops(m: dict, prompt_len: int) -> float:
    """One request's prefill: every layer over its prompt, causal
    attention, and the logits at its last position."""
    L, n = m["num_hidden_layers"], prompt_len
    mats = 2 * _layer_matmul_params(m) * n
    attn = 4 * _attn_width(m) * n * (n + 1) / 2      # QK^T and PV
    return L * (mats + attn) + 2 * m["hidden_size"] * m["vocab_size"]


def decode_flops(m: dict, depth: int) -> float:
    """One token of one slot that already holds ``depth`` rows: the new
    row attends to depth + 1 rows."""
    L = m["num_hidden_layers"]
    mats = 2 * _layer_matmul_params(m)
    attn = 4 * _attn_width(m) * (depth + 1)
    return L * (mats + attn) + 2 * m["hidden_size"] * m["vocab_size"]


def quant_dot_ops(m_rows: int, k: int, n: int) -> float:
    """Rotate (k log2 k additions per row of the k-point transform),
    quantize and multiply (m, k) by (k, n)."""
    return 2 * m_rows * k * n + m_rows * k * math.log2(k)


def hadamard_ops(rows: int, p: int) -> float:
    """The p-point transform of ``rows`` rows: the FWHT's p log2 p
    additions per row."""
    return rows * p * math.log2(p)


def roofline_time(ops: float, byt: float, peak_flops: float,
                  hbm_bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the compute
    and the memory bound."""
    return max(ops / peak_flops, byt / hbm_bytes_per_s)
