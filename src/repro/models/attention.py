"""GQA / sliding-window / cross attention with QuaRot-style rotation hooks.

The paper's end-to-end deployment (section 4.2): FP8 attention where Q and K are
Hadamard-rotated per head before quantization -- the rotation commutes out
of the QK^T product exactly (H H^T = I) while crushing per-head outliers,
and V's rotation is fused offline into (W_v, W_o) so it is free.

Online rotation points in this module (cfg.quant.rotating):
    q_r = had(q), k_r = had(k)      after RoPE, before quantize + cache
which is exactly where hadacore runs in the paper's Llama FP8 pipeline.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.api import RotationSpec
from repro.distributed.sharding import constrain
from repro.models.common import apply_rope_angles, dense_init, mrope_angles, rope_freqs


# ------------------------------------------------------------------- params
def init_attention(key, cfg, cross: bool = False):
    d, H, KH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dt),
        "wk": dense_init(ks[1], d, KH * hd, dt),
        "wv": dense_init(ks[2], d, KH * hd, dt),
        "wo": dense_init(ks[3], H * hd, d, dt, scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KH * hd,), dt)
        p["bv"] = jnp.zeros((KH * hd,), dt)
    return p


def attention_specs(cfg, cross: bool = False):
    p = {
        "wq": ("fsdp", "heads"),
        "wk": ("fsdp", "kv"),
        "wv": ("fsdp", "kv"),
        "wo": ("heads", "fsdp"),
    }
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv",), "bv": ("kv",)})
    return p


# ------------------------------------------------------------------ helpers
def _positions_angles(cfg, positions):
    """positions: (B,S) int32, or (3,B,S) for M-RoPE -> (B,S,half) angles."""
    hd = cfg.head_dim
    if cfg.mrope:
        return mrope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(hd, cfg.rope_theta)
    return ang


def _project_qkv(cfg, p, x):
    B, S, d = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(B, S, H, hd), "batch", "seq", "heads", None)
    k = constrain(k.reshape(B, S, KH, hd), "batch", "seq", "kv", None)
    v = constrain(v.reshape(B, S, KH, hd), "batch", "seq", "kv", None)
    return q, k, v


def _qk_spec(cfg, hd: int) -> RotationSpec:
    """The declarative per-head Q/K rotation site: rotate when the config
    rotates, fake-quantize when the KV cache quantizes -- one spec object
    (cached plans) instead of QuantConfig threading into free functions."""
    return RotationSpec.for_config(hd, cfg.quant)


def _v_spec(cfg, hd: int) -> RotationSpec:
    """The V site: quantize-only (V's rotation is fused offline into
    (W_v, W_o), so the online site never rotates)."""
    return RotationSpec.for_config(hd, cfg.quant, rotate=False)


def _rotate_quant_qk(cfg, q, k):
    """Paper deployment point: per-head Hadamard then low-precision Q/K.

    When both rotation and KV quantization are on, each head's rotation +
    per-token quantize run as ONE fused kernel (plan epilogue) instead of
    two HBM round trips. With bf16/fp16 models the plan's compute dtype
    keeps the transform passes in the model dtype (f32 MXU accumulation
    only -- no f32 upcast of the head_dim tiles in VMEM), so the QK path
    never touches f32 activations before the f32-accumulated score
    einsum."""
    spec = _qk_spec(cfg, q.shape[-1])
    return spec(q), spec(k)


def _sdpa(cfg, q, k, v, mask):
    """q: (B,S,H,hd), k/v: (B,T,KH,hd), mask: broadcastable (B,1,S,T) bool."""
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)
    return ctx.reshape(B, S, H * hd)


def _causal_mask(cfg, S: int, T: int):
    """Batch-independent (1,1,S,T) causal (+sliding-window) mask built from
    iota. Keeping the batch dim out of the mask matters at scale: a
    (B,1,S,S) mask becomes a multi-GB loop-carried buffer after XLA hoists
    it out of the layer scan; (1,1,S,S) stays 1/B of that."""
    q = jnp.arange(S, dtype=jnp.int32)[:, None]
    k = jnp.arange(T, dtype=jnp.int32)[None, :]
    m = k <= q
    if cfg.sliding_window:
        m &= k > (q - cfg.sliding_window)
    return m[None, None]


# ------------------------------------------------------------------ forward
@jax.named_scope("attention")
def apply_attention(
    cfg,
    p,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    causal: bool = True,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    ang = _positions_angles(cfg, positions)
    q = apply_rope_angles(q, ang)
    k = apply_rope_angles(k, ang)
    q, k = _rotate_quant_qk(cfg, q, k)
    v = _v_spec(cfg, v.shape[-1])(v)
    kvdt = cfg.quant.kv_cache_dtype(x.dtype)
    k_cache, v_cache = k.astype(kvdt), v.astype(kvdt)
    if causal:
        mask = _causal_mask(cfg, S, S)                 # (1,1,S,S)
    else:
        mask = jnp.ones((1, 1, 1, 1), bool)
    ctx = _sdpa(cfg, q, k, v, mask)
    y = ctx @ p["wo"]
    y = constrain(y, "batch", "seq", None)
    if return_kv:
        return y, (k_cache, v_cache)
    return y


@jax.named_scope("attention")
def apply_cross_attention(cfg, p, x, kv: Tuple[jnp.ndarray, jnp.ndarray]):
    """Decoder->encoder cross attention; kv precomputed (B,T,KH,hd)."""
    B, S, _ = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, H, hd)
    k, v = kv
    mask = jnp.ones((1, 1, 1, 1), bool)
    ctx = _sdpa(cfg, q, k, v, mask)
    return constrain(ctx @ p["wo"], "batch", "seq", None)


def cross_kv(cfg, p, enc_out: jnp.ndarray):
    """Precompute cross-attention K/V from encoder output (cached once)."""
    B, T, _ = enc_out.shape
    KH, hd = cfg.num_kv_heads, cfg.head_dim
    k = enc_out @ p["wk"]
    v = enc_out @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, T, KH, hd)
    v = v.reshape(B, T, KH, hd)
    # same declarative sites as the decoder QK path: K rotates+quantizes
    # (fused when the plan fuses), V quantizes only
    return _qk_spec(cfg, hd)(k), _v_spec(cfg, hd)(v)


@jax.named_scope("attention")
def decode_attention(
    cfg,
    p,
    x: jnp.ndarray,
    stack_k: jnp.ndarray,
    stack_v: jnp.ndarray,
    layer: jnp.ndarray,
    cache_pos: jnp.ndarray,
    positions: jnp.ndarray,
):
    """Single-token decode of layer ``layer`` against the stacked caches,
    which it only reads. x: (B,1,d); stack_k/v: (layers,B,T,KH,hd)
    rotated+quantized at write time (the FP8 KV-cache path); layer: ()
    int32; cache_pos: () int32 shared by the whole batch (one-shot
    serving) OR (B,) int32 per-slot positions (continuous batching: every
    slot sits at its own depth in its own KV rows, so the new row and the
    causal mask are per-row).

    Slot b's new K/V row stands in for row cache_pos[b] of the layer's
    view, so the layer attends the updated cache without writing it; the
    caller writes the rows once the layer scan is over
    (``write_kv_rows``). Returns (y, k_row, v_row), rows (B,KH,hd) in the
    cache dtype."""
    B, S, _ = x.shape
    assert S == 1
    q, k, v = _project_qkv(cfg, p, x)
    ang = _positions_angles(cfg, positions)
    q = apply_rope_angles(q, ang)
    k = apply_rope_angles(k, ang)
    q, k = _rotate_quant_qk(cfg, q, k)
    v = _v_spec(cfg, v.shape[-1])(v)
    k_row, v_row = k[:, 0].astype(stack_k.dtype), v[:, 0].astype(stack_v.dtype)
    T = stack_k.shape[2]
    pos = jnp.broadcast_to(cache_pos, (B,))            # a shared position is every slot's
    kpos = jnp.arange(T, dtype=jnp.int32)
    # the row write_kv_rows lands on: dynamic_update_slice clamps its start
    hit = (kpos[None] == jnp.clip(pos, 0, T - 1)[:, None])[:, :, None, None]

    def view(stack, row):
        c = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        return jnp.where(hit, row[:, None], c).astype(q.dtype)

    m = kpos[None] <= pos[:, None]                     # (B,T)
    if cfg.sliding_window:
        m &= kpos[None] > (pos[:, None] - cfg.sliding_window)
    mask = m[:, None, None]                            # (B,1,1,T)
    ctx = _sdpa(cfg, q, view(stack_k, k_row), view(stack_v, v_row), mask)
    y = constrain(ctx @ p["wo"], "batch", "seq", None)
    return y, k_row, v_row


def write_kv_rows(stack: jnp.ndarray, rows: jnp.ndarray, cache_pos: jnp.ndarray):
    """Write a step's new rows into a stacked cache: rows (layers,B,KH,hd)
    land in stack (layers,B,T,KH,hd) at row cache_pos[b] of slot b (a ()
    cache_pos is every slot's), every layer at once. One
    dynamic_update_slice a slot keeps the donated stack in place at any
    head count; compiled for TPU, a scatter makes the compiler relayout
    the whole stack when a chip holds few KV heads."""
    B = rows.shape[1]
    pos = jnp.broadcast_to(cache_pos, (B,))
    for b in range(B):
        stack = jax.lax.dynamic_update_slice(
            stack, rows[:, b, None, None].astype(stack.dtype), (0, b, pos[b], 0, 0))
    return stack
