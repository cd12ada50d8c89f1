"""Plain float32 reference of a dense decoder-only transformer: the
forward pass of the configurations that name ``dense_decoder``.

Written from the published descriptions (Phi-4-mini: arXiv:2412.08905;
StarCoder2: arXiv:2402.19173) in straightforward ``jax.numpy`` at
``highest`` matmul precision, with no kernel, cache or batching of the
program's. One layer at a time, its weights drawn from the seed by
``bench.weights``, over whole sequences under a causal mask. It imports
nothing of the program.

The served model applies a Walsh-Hadamard rotation to the
down-projection's input (``down_proj_rotation``, the paper's online
rotation site): with the benchmark's random weights that rotation is
part of the model, so the reference applies its own (the Sylvester
matrix, 1/sqrt(p) scale, on contiguous groups of p where the width is
not a power of two). The rotation of Q and K per head cancels
in QK^T and is left out. Departures of the program from the published
model that the reference follows are listed in each configuration's
``departures``.

``precision="int4"`` is the control: the same forward with every
matrix fake-quantized to int4 per output channel and the activations
that the program quantizes (the rotated down-projection input, the
rotated Q and K and V per head) fake-quantized to int4 per token.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

ROW_BLOCK = 128        # sequences are padded to a multiple of this


def spec(config: dict) -> dict:
    """The sizes and flags the forward needs, from a configuration file
    (Hugging Face key names)."""
    m = dict(config)
    m.setdefault("head_dim", m["hidden_size"] // m["num_attention_heads"])
    return m


def sylvester(n: int) -> np.ndarray:
    """The n x n Sylvester-Hadamard matrix of +-1 (n a power of two)."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def fwht(x, p: int):
    """Orthonormal Walsh-Hadamard transform of the last axis in
    contiguous groups of ``p`` (a power of two), as H_p = H_a (x) H_b
    (x) ... with factors of at most 128 applied one axis at a time."""
    shape = x.shape
    dims = []
    n = p
    while n > 128:
        dims.append(128)
        n //= 128
    dims = [n] + dims                    # most significant factor first
    y = x.reshape((-1, *dims))
    for axis, f in enumerate(dims, start=1):
        y = jnp.moveaxis(jnp.tensordot(y, jnp.asarray(sylvester(f)),
                                       axes=([axis], [0])), -1, axis)
    return (y.reshape(shape) * (p ** -0.5)).astype(x.dtype)


def pow2_part(n: int) -> int:
    return n & -n


def q4(x, axis):
    """Symmetric int4 fake quantization along ``axis`` (absmax / 7)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _norm(m, x, scale, bias):
    if m["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m["norm_eps"]) * scale + bias
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + m["norm_eps"]) \
        * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (L, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_weights(m, key, layer):
    """Layer ``layer``'s weights in float32."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hk = m["num_key_value_heads"] * m["head_dim"]
    shapes = {"wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
              "w_up": (d, f), "w_down": (f, d),
              "attn_norm.scale": (d,), "mlp_norm.scale": (d,)}
    if m["hidden_act"] == "silu":
        shapes["w_gate"] = (d, f)
    if m["norm"] == "layernorm":
        shapes.update({"attn_norm.bias": (d,), "mlp_norm.bias": (d,)})
    if m["qkv_bias"]:
        shapes.update({"bq": (hq,), "bk": (hk,), "bv": (hk,)})
    return {n: W.layer_leaf(key, n, layer, s, m).astype(jnp.float32)
            for n, s in shapes.items()}


def _layer(m, precision, w, x):
    """One layer over one padded sequence x (L, d); the causal mask keeps
    the padding rows at the end out of the real rows."""
    L = x.shape[0]
    H, KH, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    int4 = precision == "int4"
    mat = (lambda a: q4(a, 0)) if int4 else (lambda a: a)
    pos = jnp.arange(L)
    h = _norm(m, x, w["attn_norm.scale"], w.get("attn_norm.bias", 0.0))
    q, k, v = h @ mat(w["wq"]), h @ mat(w["wk"]), h @ mat(w["wv"])
    if m["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(L, H, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(L, KH, hd), pos, m["rope_theta"])
    v = v.reshape(L, KH, hd)
    if int4:
        q, k = q4(fwht(q, hd), -1), q4(fwht(k, hd), -1)
        v = q4(v, -1)
    qg = q.reshape(L, KH, H // KH, hd)
    s = jnp.einsum("skgd,tkd->kgst", qg, k) / jnp.sqrt(float(hd))
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    ctx = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, -1), v)
    x = x + ctx.reshape(L, H * hd) @ mat(w["wo"])
    h = _norm(m, x, w["mlp_norm.scale"], w.get("mlp_norm.bias", 0.0))
    if m["hidden_act"] == "silu":
        a = jax.nn.silu(h @ mat(w["w_gate"])) * (h @ mat(w["w_up"]))
    else:
        a = jax.nn.gelu(h @ mat(w["w_up"]), approximate=True)
    if m["down_proj_rotation"] == "hadamard":
        a = fwht(a, pow2_part(a.shape[-1]))
    if int4:
        a = q4(a, -1)
    return x + a @ mat(w["w_down"])


def _pad(seqs: Sequence[np.ndarray], n: int, length: int):
    """The sequences as an (n, length) block, zero-padded: a block of
    fixed shape compiles once for a cell, whatever the sample."""
    L = -(-length // ROW_BLOCK) * ROW_BLOCK
    if len(seqs) > n or max(len(s) for s in seqs) > L:
        raise ValueError(f"{len(seqs)} sequences of up to "
                         f"{max(len(s) for s in seqs)} do not fit ({n}, {L})")
    out = np.zeros((n, L), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def hidden_states(config: dict, seed: int, seqs: Sequence[np.ndarray],
                  block, precision: str = "f32"):
    """Final hidden states (after the final norm) of the sequences padded
    into a ``block`` = (n, length) of token ids, (n, L, d) float32."""
    m = spec(config)
    key = W.base_key(seed)
    d, V = m["hidden_size"], m["vocab_size"]

    @jax.jit
    def embed(key, toks):
        emb = W.table_rows(key, "embed", V, d, m).astype(jnp.float32)
        return jnp.take(q4(emb, -1) if precision == "int4" else emb,
                        toks, axis=0)

    @jax.jit
    def step(x, key, layer):
        w = layer_weights(m, key, layer)
        return jax.lax.map(lambda xs: _layer(m, precision, w, xs), x)

    @jax.jit
    def final(x, key):
        scale = W.top_leaf(key, "final_norm.scale", (d,), m)
        bias = (W.top_leaf(key, "final_norm.bias", (d,), m)
                if m["norm"] == "layernorm" else jnp.zeros((d,)))
        return _norm(m, x, scale.astype(jnp.float32),
                     bias.astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        x = embed(key, jnp.asarray(_pad(seqs, *block)))
        for layer in range(m["num_hidden_layers"]):
            x = step(x, key, layer)
        return final(x, key)


LOGIT_ROWS = 256       # positions per logits call, a fixed shape


def _position_chunks(h, positions):
    """Flat row indices into h of every position, in chunks of
    ``LOGIT_ROWS`` (the last padded with row 0), and the count."""
    L = h.shape[1]
    idx = np.concatenate([i * L + np.asarray(p)
                          for i, p in enumerate(positions)]).astype(np.int32)
    pad = -len(idx) % LOGIT_ROWS
    chunks = np.concatenate([idx, np.zeros(pad, np.int32)]).reshape(
        -1, LOGIT_ROWS)
    return [jnp.asarray(c) for c in chunks], len(idx)


def _table(config: dict, key, precision: str):
    m = spec(config)
    name = "embed" if m["tie_word_embeddings"] else "unembed"
    t = W.table_rows(key, name, m["vocab_size"], m["hidden_size"], m)
    t = t.astype(jnp.float32)
    return q4(t, -1) if precision == "int4" else t


def argmax_at(config: dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], block,
              precision: str = "f32"):
    """The token each position's logits put first, all sequences'
    positions in order."""
    h = hidden_states(config, seed, seqs, block, precision)
    key = W.base_key(seed)
    table = jax.jit(lambda k: _table(config, k, precision))(key)
    top = jax.jit(lambda h, i, t: (jnp.take(h.reshape(-1, h.shape[-1]), i,
                                            axis=0) @ t.T).argmax(-1))
    chunks, n = _position_chunks(h, positions)
    with jax.default_matmul_precision("highest"):
        out = [np.asarray(top(h, c, table)) for c in chunks]
    return np.concatenate(out)[:n]


def gaps_at(config: dict, seed: int, seqs: Sequence[np.ndarray],
            positions: Sequence[np.ndarray], tokens: Sequence[np.ndarray],
            block):
    """For each array of token ids in ``tokens`` (one id per position,
    all sequences' positions in order): how far below the float32
    logits' best each id's logit lies."""
    h = hidden_states(config, seed, seqs, block)
    key = W.base_key(seed)
    table = jax.jit(lambda k: _table(config, k, "f32"))(key)

    @jax.jit
    def gaps(h, i, t, toks):
        lg = jnp.take(h.reshape(-1, h.shape[-1]), i, axis=0) @ t.T
        best = lg.max(-1)
        return jnp.stack([best - jnp.take_along_axis(lg, k[:, None], -1)[:, 0]
                          for k in toks])

    chunks, n = _position_chunks(h, positions)
    toks = np.stack([np.asarray(t, np.int32) for t in tokens])
    toks = np.pad(toks, ((0, 0), (0, len(chunks) * LOGIT_ROWS - n)))
    out = []
    with jax.default_matmul_precision("highest"):
        for j, c in enumerate(chunks):
            sl = toks[:, j * LOGIT_ROWS:(j + 1) * LOGIT_ROWS]
            out.append(np.asarray(gaps(h, c, table, jnp.asarray(sl))))
    g = np.concatenate(out, axis=1)[:, :n]
    return [g[i] for i in range(len(tokens))]


def served_sequences(prompts: List[np.ndarray], outputs: List[Sequence[int]]):
    """Each request's prompt with its served tokens but the last, and the
    positions whose logits chose the served tokens."""
    seqs, pos = [], []
    for p, o in zip(prompts, outputs):
        o = np.asarray(o, np.int32)
        seqs.append(np.concatenate([p, o[:-1]]).astype(np.int32))
        pos.append(np.arange(len(p) - 1, len(p) - 1 + len(o)))
    return seqs, pos
