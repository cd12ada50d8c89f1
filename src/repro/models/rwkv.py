"""RWKV6 "Finch" (attention-free): data-dependent-decay time-mix plus
squared-ReLU channel-mix.

Time-mix recurrence (per head, state S in R^{K x V}):
    out_t = r_t (S_t + diag(u) k_t^T v_t)
    S_{t+1} = diag(w_t) S_t + k_t^T v_t
with per-channel data-dependent decay w_t = exp(-exp(w0 + lora_w(x_t))).

Training uses the exact recurrence via lax.scan over time (single while
loop in HLO -- compile-friendly at any depth); decode is the same body on
a carried state. The channel-mix down-projection gets the paper's online
Hadamard rotation (the one QuaRot insertion point an attention-free arch
keeps -- DESIGN.md section Arch-applicability).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.api import QuantDotSpec
from repro.distributed.sharding import constrain
from repro.models.common import dense_init
from repro.models.mlp import down_axes

_LORA = 32
_MIXES = 5  # r, k, v, w, g


def _dims(cfg):
    K = cfg.rwkv_head_dim
    H = cfg.d_model // K
    return H, K


def init_rwkv_tmix(key, cfg):
    d = cfg.d_model
    H, K = _dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 12)
    return {
        "mu_base": jnp.full((d,), 0.5, jnp.float32),
        "mix_w1": dense_init(ks[0], d, _MIXES * _LORA, dt, scale=0.01),
        "mix_w2": (jax.random.normal(ks[1], (_MIXES, _LORA, d), jnp.float32) * 0.01).astype(dt),
        "mu": jnp.full((_MIXES, d), 0.5, jnp.float32),
        "w0": jnp.full((d,), -2.0, jnp.float32),
        "w_lora_a": dense_init(ks[2], d, 2 * _LORA, dt, scale=0.01),
        "w_lora_b": dense_init(ks[3], 2 * _LORA, d, dt, scale=0.01),
        "u": (jax.random.normal(ks[4], (H, K), jnp.float32) * 0.1),
        "wr": dense_init(ks[5], d, d, dt),
        "wk": dense_init(ks[6], d, d, dt),
        "wv": dense_init(ks[7], d, d, dt),
        "wg": dense_init(ks[8], d, d, dt),
        "wo": dense_init(ks[9], d, d, dt, scale=1.0 / math.sqrt(d)),
        "ln_scale": jnp.ones((d,), jnp.float32),
        "ln_bias": jnp.zeros((d,), jnp.float32),
    }


def rwkv_tmix_specs(cfg):
    return {
        "mu_base": (None,), "mix_w1": ("fsdp", None), "mix_w2": (None, None, None),
        "mu": (None, None), "w0": (None,), "w_lora_a": ("fsdp", None),
        "w_lora_b": (None, None), "u": ("heads", None),
        "wr": ("fsdp", "heads"), "wk": ("fsdp", "heads"), "wv": ("fsdp", "heads"),
        "wg": ("fsdp", "heads"), "wo": ("heads", "fsdp"),
        "ln_scale": (None,), "ln_bias": (None,),
    }


def _ddlerp(p, x, x_prev):
    """Data-dependent token-shift interpolation -> the 5 mixed inputs."""
    dx = x_prev - x                                    # (B,S,d)
    base = x + dx * p["mu_base"]
    lora = jnp.tanh(base @ p["mix_w1"])                # (B,S,5*LORA)
    B, S, _ = lora.shape
    lora = lora.reshape(B, S, _MIXES, _LORA)
    dyn = jnp.einsum("bsml,mld->bsmd", lora, p["mix_w2"])  # (B,S,5,d)
    mix = p["mu"][None, None] + dyn
    out = x[:, :, None, :] + dx[:, :, None, :] * mix   # (B,S,5,d)
    return out.astype(x.dtype)


def _tmix_inputs(cfg, p, x, x_prev):
    H, K = _dims(cfg)
    B, S, d = x.shape
    m = _ddlerp(p, x, x_prev)
    xr, xk, xv, xw, xg = [m[:, :, i, :] for i in range(_MIXES)]
    r = (xr @ p["wr"]).reshape(B, S, H, K)
    k = (xk @ p["wk"]).reshape(B, S, H, K)
    v = (xv @ p["wv"]).reshape(B, S, H, K)
    g = jax.nn.silu(xg @ p["wg"])
    lw = p["w0"] + (jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(lw)).reshape(B, S, H, K)      # per-channel decay in (0,1)
    return r, k, v, g, w


def _groupnorm_heads(p, out, B, S, d):
    """Per-head LayerNorm on the wkv output (RWKV's GroupNorm)."""
    mu = out.mean(-1, keepdims=True)
    var = out.var(-1, keepdims=True)
    out = (out - mu) * jax.lax.rsqrt(var + 1e-5)
    out = out.reshape(B, S, d) * p["ln_scale"] + p["ln_bias"]
    return out


_TMIX_CHUNK = 32


def _tmix_scan(B, S, H, K, r, k, v, w, u):
    """Exact per-step recurrence (reference; O(S) sequential state I/O)."""
    rf = jnp.moveaxis(r.astype(jnp.float32), 1, 0)     # (S,B,H,K)
    kf = jnp.moveaxis(k.astype(jnp.float32), 1, 0)
    vf = jnp.moveaxis(v.astype(jnp.float32), 1, 0)
    wf = jnp.moveaxis(w.astype(jnp.float32), 1, 0)

    def step(S0, inp):
        rt, kt, vt, wt = inp                           # (B,H,K) each
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt, S0 + u[None, :, :, None] * kv)
        S1 = S0 * wt[..., None] + kv
        return S1, out

    S0 = jnp.zeros((B, H, K, K), jnp.float32)
    S_last, outs = jax.lax.scan(step, S0, (rf, kf, vf, wf))
    return jnp.moveaxis(outs, 0, 1), S_last            # (B,S,H,K)


def _tmix_chunked(B, S, H, K, r, k, v, w, u, C=_TMIX_CHUNK):
    """Chunked parallel form (GLA-style): state crosses HBM once per
    C-token chunk instead of once per token, and the intra-chunk work is
    matmul-shaped. Exact: all decay ratios are exp(<=0) computed pairwise
    in log space -- no divisions, no overflow (see EXPERIMENTS.md Perf/A).

    Per chunk (per head): out_t = (r_t (.) ew_t) S
                                + sum_{j<t} [sum_k r_tk k_jk e^{L_(t-1)k - L_jk}] v_j
                                + (r_t . u . k_t) v_t
                          S' = S (.) e^{L_(C-1)} + sum_j (k_j (.) e^{L_(C-1)-L_j}) v_j
    """
    nc = S // C
    rc = r.astype(jnp.float32).reshape(B, nc, C, H, K)
    kc = k.astype(jnp.float32).reshape(B, nc, C, H, K)
    vc = v.astype(jnp.float32).reshape(B, nc, C, H, K)
    # clamp above the f32 denormal range: CPU/TPU flush-to-zero would turn
    # log() into -inf and poison the masked pairwise differences
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-30)).reshape(B, nc, C, H, K)
    # move chunk axis first for the scan: (nc, B, C, H, K)
    rc, kc, vc, lw = (jnp.moveaxis(t, 1, 0) for t in (rc, kc, vc, lw))
    mask = jnp.tril(jnp.ones((C, C), bool), k=-1)      # j < t strictly

    def chunk_step(S0, inp):
        rci, kci, vci, lwi = inp                       # (B,C,H,K) each
        L = jnp.cumsum(lwi, axis=1)                    # inclusive within chunk
        ew = jnp.exp(L - lwi)                          # decay chunk-start -> t
        diff = (L - lwi)[:, :, None] - L[:, None]      # (B,t,j,H,K), <= 0 where valid
        diff = jnp.where(mask[None, :, :, None, None], diff, -1e30)
        D = jnp.exp(diff)                              # masked pairs -> exactly 0
        A = jnp.einsum("bthk,btjhk,bjhk->bhtj", rci, D, kci)
        out = jnp.einsum("bhtj,bjhk->bthk", A, vci)    # intra-chunk
        out += jnp.einsum("bthk,hk,bthk->bth", rci, u, kci)[..., None] * vci
        out += jnp.einsum("bthk,bhkv->bthv", rci * ew, S0)   # carry readout
        kdec = kci * jnp.exp(L[:, -1:] - L)            # k_j decayed to chunk end
        kv = jnp.einsum("bjhk,bjhv->bhkv", kdec, vci)
        S1 = S0 * jnp.exp(L[:, -1])[:, :, :, None] + kv
        return S1, out

    S0 = jnp.zeros((B, H, K, K), jnp.float32)
    S_last, outs = jax.lax.scan(chunk_step, S0, (rc, kc, vc, lw))
    outs = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, K)
    return outs, S_last


def apply_rwkv_tmix(cfg, p, x, x_prev=None, *, return_state: bool = False):
    """Full-sequence time-mix. x: (B,S,d). Uses the chunked parallel form
    when the sequence divides the chunk size (cfg.rwkv_impl='chunked'),
    falling back to the exact scan otherwise."""
    B, S, d = x.shape
    H, K = _dims(cfg)
    if x_prev is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k, v, g, w = _tmix_inputs(cfg, p, x, x_prev)
    u = p["u"]
    if cfg.rwkv_impl == "chunked" and S % cfg.rwkv_chunk == 0:
        out, S_last = _tmix_chunked(B, S, H, K, r, k, v, w, u, C=cfg.rwkv_chunk)
    else:
        out, S_last = _tmix_scan(B, S, H, K, r, k, v, w, u)
    out = _groupnorm_heads(p, out, B, S, d)
    y = (out.astype(x.dtype) * g) @ p["wo"]
    y = constrain(y, "batch", "seq", None)
    if return_state:
        return y, (S_last, x[:, -1, :])
    return y


def decode_rwkv_tmix(cfg, p, x, state):
    """Single-token step. state = (S (B,H,K,K) f32, x_prev (B,d))."""
    B, S, d = x.shape
    H, K = _dims(cfg)
    S0, xp = state
    r, k, v, g, w = _tmix_inputs(cfg, p, x, xp[:, None, :])
    rt, kt, vt, wt = (t[:, 0].astype(jnp.float32) for t in (r, k, v, w))
    kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
    out = jnp.einsum("bhk,bhkv->bhv", rt, S0 + p["u"][None, :, :, None] * kv)
    S1 = S0 * wt[..., None] + kv
    out = _groupnorm_heads(p, out[:, None].reshape(B, 1, H, K), B, 1, d)
    y = (out.astype(x.dtype) * g) @ p["wo"]
    return y, (S1, x[:, -1, :])


# ------------------------------------------------------------- channel mix
def init_rwkv_cmix(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "wr": dense_init(ks[0], d, d, dt),
        "wk": dense_init(ks[1], d, f, dt),
        "wv": dense_init(ks[2], f, d, dt, scale=1.0 / math.sqrt(f)),
    }


def rwkv_cmix_specs(cfg):
    return {"mu_r": (None,), "mu_k": (None,),
            "wr": ("fsdp", None), "wk": ("fsdp", "dff"), "wv": down_axes(cfg)}


def apply_rwkv_cmix(cfg, p, x, x_prev=None, *, return_state: bool = False):
    if x_prev is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    dx = x_prev - x
    xr = (x + dx * p["mu_r"]).astype(x.dtype)
    xk = (x + dx * p["mu_k"]).astype(x.dtype)
    r = jax.nn.sigmoid(xr @ p["wr"])
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    k = constrain(k, "batch", "seq", "dff")
    # the paper's online rotation point (down-projection input): rotate +
    # per-token quantize + the real int8/fp8 contraction run as one fused
    # rotate-once quant_dot kernel when the plan supports it (no f32
    # fake-quant, no HBM round trip of the rotated tensor, each row block
    # transformed once for ALL weight tiles -- DESIGN.md section 8).
    # Declared as a spec: a pre-quantized QTensor 'wv' is consumed
    # directly on the serving path; under a mesh the dispatch shard_maps
    # with row-sharded activations and the fused kernel shard-local.
    spec = QuantDotSpec.for_config(k.shape[-1], cfg.quant,
                                   weight_axes=down_axes(cfg))
    y = r * spec.bind(p["wv"])(k)
    y = constrain(y, "batch", "seq", None)
    if return_state:
        return y, x[:, -1, :]
    return y


def decode_rwkv_cmix(cfg, p, x, x_prev):
    y = apply_rwkv_cmix(cfg, p, x, x_prev[:, None, :])
    return y, x[:, -1, :]
