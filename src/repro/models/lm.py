"""Model assembly: superblock-scanned LM covering all ten architectures.

A config's ``groups`` is a list of (pattern, repeats); each pattern is a
superblock of layer kinds. Parameters for each position in the pattern are
stacked over ``repeats`` and the whole group runs as one ``lax.scan`` --
126-layer models trace a single superblock body. Heterogeneous stacks
(zamba2, llama4) are exactly why the superblock abstraction exists.

Entry points:
    init_lm / lm_param_specs     parameters + logical sharding tree
    lm_loss                      training forward + CE (+ MoE aux)
    lm_prefill                   forward returning logits + KV/state caches
    lm_decode_step               single-token decode on the caches

Every compiled instruction carries a ``jax.named_scope`` path in its
op_name metadata: ``embed``, ``layers`` (each group's scan), within it
``attention`` and ``mlp``, then ``logits``. What falls in ``layers`` but
in neither block is the scan's own work: slicing each layer's weights
and caches out of the stacks and stacking the new caches back.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.wquant import dequant_tree
from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models.common import apply_norm, dense_init, init_norm, sinusoidal_positions
from repro.models.config import ModelConfig

# --------------------------------------------------------------- per-kind
_KIND_HAS_ATTN = {"attn": True, "moe": True, "xattn": True, "enc_attn": True,
                  "mamba": False, "rwkv": False}


def _init_block(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if kind in ("attn", "moe", "enc_attn"):
        p = {"norm1": init_norm(cfg, d), "attn": A.init_attention(ks[0], cfg),
             "norm2": init_norm(cfg, d)}
        p["moe" if kind == "moe" else "mlp"] = (
            M.init_moe(ks[1], cfg) if kind == "moe" else M.init_mlp(ks[1], cfg))
        return p
    if kind == "xattn":
        return {"norm1": init_norm(cfg, d), "attn": A.init_attention(ks[0], cfg),
                "norm_x": init_norm(cfg, d), "xattn": A.init_attention(ks[1], cfg, cross=True),
                "norm2": init_norm(cfg, d), "mlp": M.init_mlp(ks[2], cfg)}
    if kind == "mamba":
        return {"norm1": init_norm(cfg, d), "mamba": S.init_mamba(ks[0], cfg)}
    if kind == "rwkv":
        return {"norm1": init_norm(cfg, d), "tmix": R.init_rwkv_tmix(ks[0], cfg),
                "norm2": init_norm(cfg, d), "cmix": R.init_rwkv_cmix(ks[1], cfg)}
    raise ValueError(kind)


def _block_specs(cfg: ModelConfig, kind: str):
    n1 = {"scale": (None,)} if cfg.norm == "rmsnorm" else {"scale": (None,), "bias": (None,)}
    if kind in ("attn", "moe", "enc_attn"):
        p = {"norm1": dict(n1), "attn": A.attention_specs(cfg), "norm2": dict(n1)}
        p["moe" if kind == "moe" else "mlp"] = (
            M.moe_specs(cfg) if kind == "moe" else M.mlp_specs(cfg))
        return p
    if kind == "xattn":
        return {"norm1": dict(n1), "attn": A.attention_specs(cfg),
                "norm_x": dict(n1), "xattn": A.attention_specs(cfg, cross=True),
                "norm2": dict(n1), "mlp": M.mlp_specs(cfg)}
    if kind == "mamba":
        return {"norm1": dict(n1), "mamba": S.mamba_specs(cfg)}
    if kind == "rwkv":
        return {"norm1": dict(n1), "tmix": R.rwkv_tmix_specs(cfg),
                "norm2": dict(n1), "cmix": R.rwkv_cmix_specs(cfg)}
    raise ValueError(kind)


def _apply_block_train(cfg, kind, p, x, positions, enc_out, want_cache: bool):
    """Full-seq block. Returns (x, aux, cache_tree_or_None)."""
    cache = None
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "moe", "enc_attn"):
        h = apply_norm(cfg, p["norm1"], x)
        causal = kind != "enc_attn"
        if want_cache and causal:
            y, (ck, cv) = A.apply_attention(cfg, p["attn"], h, positions,
                                            causal=True, return_kv=True)
            cache = {"k": ck, "v": cv}
        else:
            y = A.apply_attention(cfg, p["attn"], h, positions, causal=causal)
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, aux = M.apply_moe(cfg, p["moe"], h)
        else:
            y = M.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    elif kind == "xattn":
        h = apply_norm(cfg, p["norm1"], x)
        if want_cache:
            y, (ck, cv) = A.apply_attention(cfg, p["attn"], h, positions,
                                            causal=True, return_kv=True)
        else:
            y = A.apply_attention(cfg, p["attn"], h, positions, causal=True)
        x = x + y
        h = apply_norm(cfg, p["norm_x"], x)
        xkv = A.cross_kv(cfg, p["xattn"], enc_out)
        x = x + A.apply_cross_attention(cfg, p["xattn"], h, xkv)
        if want_cache:
            cache = {"k": ck, "v": cv, "xk": xkv[0], "xv": xkv[1]}
        h = apply_norm(cfg, p["norm2"], x)
        x = x + M.apply_mlp(cfg, p["mlp"], h)
    elif kind == "mamba":
        h = apply_norm(cfg, p["norm1"], x)
        if want_cache:
            y, st = S.apply_mamba(cfg, p["mamba"], h, return_state=True)
            cache = {"ssm": st.ssm, "conv_x": st.conv_x, "conv_bc": st.conv_bc}
        else:
            y = S.apply_mamba(cfg, p["mamba"], h)
        x = x + y
    elif kind == "rwkv":
        h = apply_norm(cfg, p["norm1"], x)
        if want_cache:
            y, (st, xp) = R.apply_rwkv_tmix(cfg, p["tmix"], h, return_state=True)
            cache = {"S": st, "xp_t": xp}
        else:
            y = R.apply_rwkv_tmix(cfg, p["tmix"], h)
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        if want_cache:
            y, xpc = R.apply_rwkv_cmix(cfg, p["cmix"], h, return_state=True)
            cache["xp_c"] = xpc
        else:
            y = R.apply_rwkv_cmix(cfg, p["cmix"], h)
        x = x + y
    else:
        raise ValueError(kind)
    return x, aux, cache


def _apply_block_decode(cfg, kind, p, x, cache, cache_pos, positions, enc_out):
    """Single-token block step. Returns (x, new_cache)."""
    if kind in ("attn", "moe"):
        h = apply_norm(cfg, p["norm1"], x)
        y, ck, cv = A.decode_attention(cfg, p["attn"], h, cache["k"], cache["v"],
                                       cache_pos, positions)
        x = x + y
        new = {"k": ck, "v": cv}
        h = apply_norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, _ = M.apply_moe(cfg, p["moe"], h)
        else:
            y = M.apply_mlp(cfg, p["mlp"], h)
        x = x + y
    elif kind == "xattn":
        h = apply_norm(cfg, p["norm1"], x)
        y, ck, cv = A.decode_attention(cfg, p["attn"], h, cache["k"], cache["v"],
                                       cache_pos, positions)
        x = x + y
        h = apply_norm(cfg, p["norm_x"], x)
        x = x + A.apply_cross_attention(cfg, p["xattn"], h, (cache["xk"], cache["xv"]))
        new = {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}
        h = apply_norm(cfg, p["norm2"], x)
        x = x + M.apply_mlp(cfg, p["mlp"], h)
    elif kind == "mamba":
        h = apply_norm(cfg, p["norm1"], x)
        st = S.MambaState(cache["ssm"], cache["conv_x"], cache["conv_bc"])
        y, st = S.decode_mamba(cfg, p["mamba"], h, st)
        x = x + y
        new = {"ssm": st.ssm, "conv_x": st.conv_x, "conv_bc": st.conv_bc}
    elif kind == "rwkv":
        h = apply_norm(cfg, p["norm1"], x)
        y, (st, xp) = R.decode_rwkv_tmix(cfg, p["tmix"], h, (cache["S"], cache["xp_t"]))
        x = x + y
        h = apply_norm(cfg, p["norm2"], x)
        y, xpc = R.decode_rwkv_cmix(cfg, p["cmix"], h, cache["xp_c"])
        x = x + y
        new = {"S": st, "xp_t": xp, "xp_c": xpc}
    else:
        raise ValueError(kind)
    return x, new


# ----------------------------------------------------------------- stacks
def _init_group(key, cfg, pattern, repeats):
    ks = jax.random.split(key, len(pattern))
    g = {}
    for j, kind in enumerate(pattern):
        g[f"p{j}"] = jax.vmap(lambda k, kd=kind: _init_block(k, cfg, kd))(
            jax.random.split(ks[j], repeats))
    return g


def _group_specs(cfg, pattern):
    return {f"p{j}": jax.tree.map(lambda t: ("layers",) + t,
                                  _block_specs(cfg, kind),
                                  is_leaf=lambda t: isinstance(t, tuple))
            for j, kind in enumerate(pattern)}


def _maybe_remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
              else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy)


def _dequant_layer(cfg, lp, specs, dtype):
    """Dequantize a layer slice. Quantized weights are first constrained
    with their fsdp dims dropped, forcing GSPMD to all-gather the 1-byte
    tensor and dequantize shard-locally -- weight wire traffic stays
    1 byte/elem.

    QTensor leaves at quant_dot CONSUMER sites (down-projection weights,
    when the config's rotation-quantization matches their storage mode)
    are kept quantized: the spec-bound quant_dot in the block consumes
    q/scale directly, so the serving forward never re-quantizes (or even
    dequantizes) those weights per step."""
    from repro.core.wquant import _is_consumer, is_qleaf

    qc = cfg.quant

    def keep(keys, p) -> bool:
        return (qc.rotating and qc.enabled and p.mode == qc.mode
                and _is_consumer(keys))

    def one(spec_or_sub, p, keys):
        if is_qleaf(p):
            if keep(keys, p):
                return p
            spec = spec_or_sub.q if is_qleaf(spec_or_sub) else spec_or_sub
            gather_spec = tuple(None if a == "fsdp" else a for a in spec[1:])
            wq = constrain(p.q, *gather_spec)
            return (wq.astype(jnp.float32) * p.scale).astype(dtype)
        if isinstance(p, dict):
            return {k: one(spec_or_sub[k] if isinstance(spec_or_sub, dict) else spec_or_sub,
                           v, keys + (k,)) for k, v in p.items()}
        return p

    return {k: one(specs[k], v, (k,)) for k, v in lp.items()}


def _run_stack(cfg, groups_cfg, gparams, x, positions, enc_out,
               want_cache: bool):
    """Scan every group; returns (x, aux_total, caches or None)."""
    aux_total = jnp.zeros((), jnp.float32)
    caches = []
    for (pattern, repeats), gp in zip(groups_cfg, gparams):
        gspecs = _group_specs(cfg, pattern) if cfg.weight_quant == "int8" else None
        def body(x, layer_params, _pattern=pattern):
            aux_sb = jnp.zeros((), jnp.float32)
            cache_out = {}
            for j, kind in enumerate(_pattern):
                x, aux, cache = _apply_block_train(
                    cfg, kind, layer_params[f"p{j}"], x, positions, enc_out,
                    want_cache)
                aux_sb = aux_sb + aux
                if want_cache:
                    cache_out[f"p{j}"] = cache
            return x, (aux_sb, cache_out)

        body = _maybe_remat(cfg, body)

        def scan_body(carry, lp):
            x = carry
            # int8-stored weights dequantize HERE -- after the per-layer
            # slice is fetched/gathered, so FSDP wire traffic stays int8
            if gspecs is not None:
                lp = _dequant_layer(cfg, lp, gspecs, x.dtype)
            else:
                lp = dequant_tree(lp, x.dtype)
            x, (aux, cache) = body(x, lp)
            # Megatron-SP style: the residual stream carried between layers
            # (and saved for the backward scan) can be sequence-sharded over
            # the TP axis -- rules override {"seqpar": "model"}. Activations
            # are gathered inside the block where attention needs full seq.
            x = constrain(x, "batch", "seqpar", None)
            return x, (aux, cache)

        with jax.named_scope("layers"):
            x, (auxes, cache_stack) = jax.lax.scan(scan_body, x, gp)
        aux_total = aux_total + auxes.sum()
        caches.append(cache_stack if want_cache else None)
    return x, aux_total, caches


# ------------------------------------------------------------------ model
def init_lm(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4 + len(cfg.groups) + len(cfg.encoder_groups))
    params: Dict[str, Any] = {
        "emb": dense_init(ks[0], cfg.padded_vocab, cfg.d_model, dt, scale=0.02),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unemb"] = dense_init(ks[1], cfg.d_model, cfg.padded_vocab, dt)
    params["groups"] = [
        _init_group(ks[4 + i], cfg, pat, rep)
        for i, (pat, rep) in enumerate(cfg.groups)]
    if cfg.is_encdec:
        params["enc_groups"] = [
            _init_group(ks[4 + len(cfg.groups) + i], cfg, pat, rep)
            for i, (pat, rep) in enumerate(cfg.encoder_groups)]
        params["enc_norm"] = init_norm(cfg, cfg.d_model)
    return params


def lm_param_specs(cfg: ModelConfig):
    n1 = {"scale": (None,)} if cfg.norm == "rmsnorm" else {"scale": (None,), "bias": (None,)}
    specs: Dict[str, Any] = {
        "emb": ("vocab", "embed"),
        "final_norm": dict(n1),
    }
    if not cfg.tie_embeddings:
        specs["unemb"] = ("embed", "vocab")
    specs["groups"] = [_group_specs(cfg, pat) for pat, _ in cfg.groups]
    if cfg.is_encdec:
        specs["enc_groups"] = [_group_specs(cfg, pat) for pat, _ in cfg.encoder_groups]
        specs["enc_norm"] = dict(n1)
    return specs


@jax.named_scope("embed")
def _embed_inputs(cfg, params, batch):
    """Build (x, positions) for the decoder stack from the input batch."""
    tokens = batch["tokens"]                       # (B, S_tok)
    emb = dequant_tree(params["emb"], jnp.dtype(cfg.dtype))
    x = jnp.take(emb, tokens, axis=0)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)  # (B, P, d)
        x = jnp.concatenate([pe, x], axis=1)
    B, St = x.shape[0], x.shape[1]
    if cfg.mrope:
        positions = batch["positions"]             # (3, B, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32)[None], (B, St))
    if cfg.is_encdec:
        x = x + sinusoidal_positions(St, cfg.d_model).astype(x.dtype)[None]
    x = constrain(x, "batch", "seq", None)
    return x, positions


def _run_encoder(cfg, params, frames):
    """Whisper encoder on precomputed frame embeddings (conv frontend stub)."""
    B, T, _ = frames.shape
    x = frames + sinusoidal_positions(T, cfg.d_model).astype(frames.dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x, _, _ = _run_stack(cfg, cfg.encoder_groups, params["enc_groups"], x, pos,
                         None, want_cache=False)
    return apply_norm(cfg, params["enc_norm"], x)


@jax.named_scope("logits")
def _logits(cfg, params, x):
    x = apply_norm(cfg, params["final_norm"], x)
    w = dequant_tree(params["emb"] if cfg.tie_embeddings else params["unemb"],
                     x.dtype)
    logits = x @ (w.T if cfg.tie_embeddings else w)
    if cfg.padded_vocab != cfg.vocab_size:
        valid = jnp.arange(cfg.padded_vocab, dtype=jnp.int32) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-jnp.inf, logits.dtype))
    return constrain(logits, "batch", "seq", "vocab")


def lm_forward(cfg: ModelConfig, params, batch, want_cache: bool = False):
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(cfg, params, batch["frames"].astype(jnp.dtype(cfg.dtype)))
    x, positions = _embed_inputs(cfg, params, batch)
    x, aux, caches = _run_stack(cfg, cfg.groups, params["groups"], x, positions,
                                enc_out, want_cache)
    return _logits(cfg, params, x), aux, caches


def lm_loss(cfg: ModelConfig, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux, _ = lm_forward(cfg, params, batch)
    labels = batch["labels"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        P = batch["patch_embeds"].shape[1]
        logits = logits[:, P:]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


def lm_prefill(cfg: ModelConfig, params, batch):
    """Forward pass returning (last-position logits, caches, enc_out)."""
    logits, _, caches = lm_forward(cfg, params, batch, want_cache=True)
    return logits[:, -1:], caches


def pad_kv_caches(cfg, caches, max_len: int):
    """Grow attention K/V caches along seq to max_len for generation."""
    out = []
    for cache_stack in caches:
        new = {}
        for k, tree in cache_stack.items():
            if tree is not None and "k" in tree:
                t = dict(tree)
                for key in ("k", "v"):
                    arr = t[key]
                    pad = max_len - arr.shape[2]
                    if pad > 0:
                        t[key] = jnp.pad(arr, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                new[k] = t
            else:
                new[k] = tree
        out.append(new)
    return out


def lm_decode_step(cfg: ModelConfig, params, caches, tokens, cache_pos):
    """One decode step. tokens: (B,1) int32; cache_pos: () int32 (number of
    tokens already in the cache, shared by the whole batch) OR (B,) int32
    per-slot positions -- the continuous-batching form, where every batch
    row is an independent request slot at its own depth (serving.engine).
    Returns (logits, new_caches)."""
    with jax.named_scope("embed"):
        emb = dequant_tree(params["emb"], jnp.dtype(cfg.dtype))
        x = jnp.take(emb, tokens, axis=0)
    B = x.shape[0]
    if cfg.is_encdec:
        x = x + sinusoidal_positions(1, cfg.d_model).astype(x.dtype)[None]
    if cache_pos.ndim == 1:
        pos = cache_pos[:, None].astype(jnp.int32)     # (B,1) per-slot
    else:
        pos = jnp.broadcast_to(cache_pos[None, None], (B, 1)).astype(jnp.int32)
    if cfg.mrope:
        positions = jnp.broadcast_to(pos[None], (3, B, 1))
    else:
        positions = pos
    x = constrain(x, "batch", "seq", None)

    new_caches = []
    for (pattern, repeats), gp, cache_stack in zip(cfg.groups, params["groups"], caches):
        gspecs = _group_specs(cfg, pattern) if cfg.weight_quant == "int8" else None

        def body(x, inp, _pattern=pattern, _gspecs=gspecs):
            lp, lc = inp
            if _gspecs is not None:
                lp = _dequant_layer(cfg, lp, _gspecs, x.dtype)
            else:
                lp = dequant_tree(lp, x.dtype)
            new_c = {}
            for j, kind in enumerate(_pattern):
                x, nc = _apply_block_decode(cfg, kind, lp[f"p{j}"], x,
                                            lc[f"p{j}"], cache_pos, positions, None)
                new_c[f"p{j}"] = nc
            return x, new_c

        with jax.named_scope("layers"):
            x, new_stack = jax.lax.scan(body, x, (gp, cache_stack))
        new_caches.append(new_stack)
    return _logits(cfg, params, x), new_caches
