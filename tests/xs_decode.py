"""The decode step as it stood before the layer scan read the KV cache
in place: each group scans ``(params, caches)`` as xs, every layer writes
its new rows into its own slice and the updated slices come back as ys,
stacked into new caches. Kept as the reference for bitwise parity
(``tests/test_decode_cache.py``) and as the xs-scanned step the
``scan-cache-xs`` lint rule must flag (``tests/test_analysis.py``)."""
import jax
import jax.numpy as jnp

from repro.core.wquant import dequant_tree
from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import lm as L
from repro.models import mlp as M
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models.common import apply_norm, apply_rope_angles, sinusoidal_positions


def _decode_attention(cfg, p, x, cache_k, cache_v, cache_pos, positions):
    q, k, v = A._project_qkv(cfg, p, x)
    ang = A._positions_angles(cfg, positions)
    q = apply_rope_angles(q, ang)
    k = apply_rope_angles(k, ang)
    q, k = A._rotate_quant_qk(cfg, q, k)
    v = A._v_spec(cfg, v.shape[-1])(v)
    per_slot = cache_pos.ndim == 1
    if per_slot:
        write = jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(
            c, u, s, axis=0))
        cache_k = write(cache_k, k.astype(cache_k.dtype), cache_pos)
        cache_v = write(cache_v, v.astype(cache_v.dtype), cache_pos)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), cache_pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), cache_pos, axis=1)
    T = cache_k.shape[1]
    kpos = jnp.arange(T, dtype=jnp.int32)
    if per_slot:
        m = kpos[None] <= cache_pos[:, None]
        if cfg.sliding_window:
            m &= kpos[None] > (cache_pos[:, None] - cfg.sliding_window)
        mask = m[:, None, None]
    else:
        m = kpos <= cache_pos
        if cfg.sliding_window:
            m &= kpos > (cache_pos - cfg.sliding_window)
        mask = m[None, None, None]
    ctx = A._sdpa(cfg, q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask)
    y = constrain(ctx @ p["wo"], "batch", "seq", None)
    return y, cache_k, cache_v


def _block(cfg, kind, p, x, cache, cache_pos, positions):
    if kind in ("attn", "moe"):
        h = apply_norm(cfg, p["norm1"], x)
        y, ck, cv = _decode_attention(cfg, p["attn"], h, cache["k"], cache["v"],
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
        y, ck, cv = _decode_attention(cfg, p["attn"], h, cache["k"], cache["v"],
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


def xs_decode_step(cfg, params, caches, tokens, cache_pos):
    """``lm_decode_step``'s signature and results, with the caches
    scanned as xs/ys."""
    with jax.named_scope("embed"):
        emb = dequant_tree(params["emb"], jnp.dtype(cfg.dtype))
        x = jnp.take(emb, tokens, axis=0)
    B = x.shape[0]
    if cfg.is_encdec:
        x = x + sinusoidal_positions(1, cfg.d_model).astype(x.dtype)[None]
    if cache_pos.ndim == 1:
        pos = cache_pos[:, None].astype(jnp.int32)
    else:
        pos = jnp.broadcast_to(cache_pos[None, None], (B, 1)).astype(jnp.int32)
    positions = jnp.broadcast_to(pos[None], (3, B, 1)) if cfg.mrope else pos
    x = constrain(x, "batch", "seq", None)

    new_caches = []
    for (pattern, _), gp, cache_stack in zip(cfg.groups, params["groups"], caches):
        gspecs = L._group_specs(cfg, pattern) if cfg.weight_quant == "int8" else None

        def body(x, inp, _pattern=pattern, _gspecs=gspecs):
            lp, lc = inp
            if _gspecs is not None:
                lp = L._dequant_layer(cfg, lp, _gspecs, x.dtype)
            else:
                lp = dequant_tree(lp, x.dtype)
            new_c = {}
            for j, kind in enumerate(_pattern):
                x, new_c[f"p{j}"] = _block(cfg, kind, lp[f"p{j}"], x, lc[f"p{j}"],
                                           cache_pos, positions)
            return x, new_c

        with jax.named_scope("layers"):
            x, new_stack = jax.lax.scan(body, x, (gp, cache_stack))
        new_caches.append(new_stack)
    return L._logits(cfg, params, x), new_caches
