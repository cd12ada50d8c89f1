"""The benchmark's checkpoint: every weight a pure function of the seed,
a canonical name and a layer index, so that the program's loader and the
plain reference draw the same numbers without sharing anything but this
module. Values are normal draws times a per-name scale, rounded to
bfloat16 (the checkpoint's precision); norm scales are 1 + 0.1·N, biases
0.02·N. Embedding and unembedding rows are drawn one vocabulary id at a
time, so a padded table and an unpadded one agree on every real row.
Nothing here imports the program."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from any whole seed: PRNGKey keeps only 32 bits, so the
    high bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def scale(name: str, model: dict) -> float:
    """Standard deviation of matrix ``name``: 1/sqrt(fan-in), and 0.02
    for the embedding."""
    if name == "embed":
        return 0.02
    d, f = model["hidden_size"], model["intermediate_size"]
    hq = model["num_attention_heads"] * model["head_dim"]
    return {"wq": d, "wk": d, "wv": d, "wo": hq, "w_gate": d, "w_up": d,
            "w_down": f, "unembed": d}[name] ** -0.5


def _draw(key, name: str, shape, model: dict):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(".scale"):
        v = 1.0 + 0.1 * z
    elif name.endswith(".bias") or name in ("bq", "bk", "bv"):
        v = 0.02 * z
    else:
        v = z * scale(name, model)
    return v.astype(jnp.bfloat16)


def layer_leaf(key, name: str, layer, shape, model: dict):
    """Layer ``layer``'s weight ``name`` (bfloat16)."""
    return _draw(jax.random.fold_in(_name_key(key, name), layer), name,
                 shape, model)


def stacked_leaf(key, name: str, layers: int, shape, model: dict):
    """``layer_leaf`` for every layer, stacked on a leading axis."""
    return jax.vmap(lambda l: layer_leaf(key, name, l, shape, model))(
        jnp.arange(layers))


def table_rows(key, name: str, rows: int, width: int, model: dict,
               start: int = 0):
    """Rows ``start .. start+rows`` of the table ``name`` ('embed' or
    'unembed'), one draw per vocabulary id."""
    k = _name_key(key, name)
    return jax.vmap(lambda r: _draw(jax.random.fold_in(k, r), name,
                                    (width,), model))(
        start + jnp.arange(rows))


def top_leaf(key, name: str, shape, model: dict):
    """A weight outside the layers (the final norm)."""
    return _draw(_name_key(key, name), name, shape, model)
