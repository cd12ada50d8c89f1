"""MLPs: SwiGLU / GELU dense blocks and top-k MoE, with the QuaRot online
Hadamard on the down-projection input -- the red "online rotation" block in
the paper's Fig. 1, and hadacore's primary insertion point.

MoE uses GShard-style capacity-factor dense dispatch (one-hot dispatch /
combine einsums): it shards cleanly under GSPMD (experts on the 'model'
axis when divisible, expert-ffn otherwise) and needs no ragged ops at
dry-run scale. All experts share one Hadamard (same d_ff), so the online
rotation is applied once to the dispatched activations.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.api import QuantDotSpec
from repro.distributed.sharding import constrain
from repro.models.common import dense_init

# Logical sharding axes of the down-projection weights -- the declarative
# half of the consumer spec: under a mesh the out-channel axis folds into
# the quant_dot plan key and dispatch shards over it. Where the site
# rotates and quantizes, the Hadamard spans d_ff, so the weight is stored
# by out-channel only ('qdout'): a tensor-parallel mesh, which would
# otherwise hold it split over d_ff, never gathers it for the call.
_DOWN_AXES = ("dff", "fsdp")
_ROTATED_DOWN_AXES = (None, "qdout")
_EXPERT_DOWN_AXES = ("experts", "dff", "fsdp")


def down_axes(cfg):
    """Logical axes of a dense down-projection weight (d_ff, d_model)."""
    qc = cfg.quant
    return _ROTATED_DOWN_AXES if qc.rotating and qc.enabled else _DOWN_AXES


def _act(cfg, g):
    return jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)


# -------------------------------------------------------------------- dense
def init_mlp(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[1], d, f, dt),
         "w_down": dense_init(ks[2], f, d, dt, scale=1.0 / math.sqrt(f))}
    if cfg.act == "swiglu":
        p["w_gate"] = dense_init(ks[0], d, f, dt)
    return p


def mlp_specs(cfg):
    p = {"w_up": ("fsdp", "dff"), "w_down": down_axes(cfg)}
    if cfg.act == "swiglu":
        p["w_gate"] = ("fsdp", "dff")
    return p


@jax.named_scope("mlp")
def apply_mlp(cfg, p, x):
    qc = cfg.quant
    h = _act(cfg, x @ p["w_gate"]) * (x @ p["w_up"]) if cfg.act == "swiglu" \
        else _act(cfg, x @ p["w_up"])
    h = constrain(h, "batch", "seq", "dff")
    # ---- the paper's online rotation: Hadamard on the down_proj input,
    # fused with the activation quantization AND the int8/fp8 down-proj
    # GEMM in one rotate-once quant_dot kernel when the plan supports it
    # (each row block is transformed exactly once and served to every
    # weight tile from VMEM scratch -- DESIGN.md section 8). The site is
    # declared as a spec and bound to the weight: a raw weight quantizes
    # on the fly (training), a pre-quantized QTensor is consumed directly
    # (serving -- zero per-forward weight quantization). Under a mesh the
    # dispatch shard_maps: activations row-sharded over the data axes,
    # weight columns + scales over 'qdout', the fused kernel shard-local --
    spec = QuantDotSpec.for_config(h.shape[-1], qc, weight_axes=down_axes(cfg))
    y = spec.bind(p["w_down"])(h)
    return constrain(y, "batch", "seq", None)


# ---------------------------------------------------------------------- MoE
def init_moe(key, cfg):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)

    def expert(k):
        kk = jax.random.split(k, 3)
        return {"w_gate": dense_init(kk[0], d, f, dt),
                "w_up": dense_init(kk[1], d, f, dt),
                "w_down": dense_init(kk[2], f, d, dt, scale=1.0 / math.sqrt(f))}

    p = {"router": dense_init(ks[0], d, E, jnp.float32),
         "experts": jax.vmap(expert)(jax.random.split(ks[1], E))}
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(ks[2], cfg)
    return p


def moe_specs(cfg):
    p = {"router": ("fsdp", None),
         "experts": {"w_gate": ("experts", "fsdp", "dff"),
                     "w_up": ("experts", "fsdp", "dff"),
                     "w_down": ("experts", "dff", "fsdp")}}
    if cfg.moe_shared_expert:
        p["shared"] = mlp_specs(cfg)
    return p


@jax.named_scope("mlp")
def apply_moe(cfg, p, x):
    """x: (B,S,d). Top-k routing with capacity-factor dense dispatch."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    qc = cfg.quant
    cap = max(1, int(cfg.capacity_factor * S * K / E))

    logits = (x.astype(jnp.float32) @ p["router"])          # (B,S,E)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, K)                    # (B,S,K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # expert assignment mask (B,S,K,E) and within-expert position via cumsum
    sel = jax.nn.one_hot(topi, E, dtype=jnp.float32)        # (B,S,K,E)
    flat = sel.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                   # tokens before me
    pos = pos.reshape(B, S, K, E)
    keep = sel * (pos < cap)                                # capacity dropping
    posc = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    cap1h = jax.nn.one_hot(posc, cap, dtype=jnp.float32)    # (B,S,K,E,cap)
    dispatch = (keep[..., None] * cap1h).sum(2)             # (B,S,E,cap)
    combine = (keep * topw[..., None])[..., None] * cap1h   # (B,S,K,E,cap)
    combine = combine.sum(2)                                # (B,S,E,cap)

    xin = jnp.einsum("bsec,bsd->becd", dispatch.astype(x.dtype), x)
    xin = constrain(xin, "moebatch", "experts", None, None)
    we = p["experts"]
    g = jnp.einsum("becd,edf->becf", xin, we["w_gate"])
    u = jnp.einsum("becd,edf->becf", xin, we["w_up"])
    h = _act(cfg, g) * u
    h = constrain(h, "moebatch", "experts", None, "dff")
    # shared online Hadamard (all experts share d_ff) + REAL int8/fp8
    # expert down-proj with int32/f32 accumulation -- no f32 fake-quant
    # on the hot path. Off-mesh this is ONE 3-D rotate-once pallas
    # kernel (rotation + quantize + every expert's contraction, no HBM
    # round trip of (q, scales) -- DESIGN.md section 8); under a mesh
    # the einsum form runs and shards under GSPMD (not the 2-D
    # shard_map dispatch). Pre-quantized QTensor expert weights
    # (per-(expert, out-channel) scales) are consumed directly;
    # weight_axes here is declarative metadata for the site.
    spec = QuantDotSpec.for_config(h.shape[-1], qc,
                                   weight_axes=_EXPERT_DOWN_AXES)
    yout = spec.bind_experts(we["w_down"])(h)
    y = jnp.einsum("bsec,becd->bsd", combine.astype(x.dtype), yout)
    y = constrain(y, "batch", "seq", None)

    if cfg.moe_shared_expert:
        y = y + apply_mlp(cfg, p["shared"], x)
    # load-balancing auxiliary loss (Switch-style), returned for training
    density = sel.sum(2).mean(axis=(0, 1))                  # (E,)
    router_prob = gates.mean(axis=(0, 1))
    aux = E * jnp.sum(density * router_prob)
    return y, aux
