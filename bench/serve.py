"""The system under test, as the benchmark builds it: a ``ModelConfig``
from a configuration file, the program's parameter tree filled from the
benchmark's checkpoint (``bench.weights``) and quantized by the
program's own load path in one jitted call on the device, and a
``ServeEngine`` at the traffic mix's slots and lengths."""
from __future__ import annotations

import dataclasses

import jax

from bench import weights as W

# the program's layer-leaf names -> the checkpoint's canonical names
_LAYER_NAMES = {
    ("norm1", "scale"): "attn_norm.scale", ("norm1", "bias"): "attn_norm.bias",
    ("norm2", "scale"): "mlp_norm.scale", ("norm2", "bias"): "mlp_norm.bias",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("attn", "bq"): "bq", ("attn", "bk"): "bk",
    ("attn", "bv"): "bv", ("mlp", "w_gate"): "w_gate",
    ("mlp", "w_up"): "w_up", ("mlp", "w_down"): "w_down",
}
_ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    repo's preset it names, with every size the file states."""
    from repro.configs import get_config
    from repro.core.quant import QuantConfig

    prog = config["program"]
    q = prog["quant"]
    cfg = dataclasses.replace(
        get_config(prog["preset"]),
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim"),
        vocab_size=config["vocab_size"],
        groups=((("attn",), config["num_hidden_layers"]),),
        rope_theta=float(config["rope_theta"]),
        act=_ACTS[config["hidden_act"]], norm=config["norm"],
        qkv_bias=config["qkv_bias"],
        tie_embeddings=config["tie_word_embeddings"],
        weight_quant=prog["weight_quant"])
    if cfg.head_dim is None:
        cfg = dataclasses.replace(
            cfg, head_dim=cfg.d_model // cfg.num_heads)
    return cfg.with_quant(QuantConfig(
        mode=q["mode"], rotate=q["rotate"], backend=q["backend"],
        kv_quant=q["kv_quant"], schedule=q.get("schedule")))


def _leaf_values(key, path, sds, model, padded_vocab):
    keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if keys[0] == "groups":
        name = _LAYER_NAMES.get(tuple(keys[-2:]))
        if name is None:
            raise KeyError(f"no checkpoint name for program leaf {keys}")
        v = W.stacked_leaf(key, name, sds.shape[0], sds.shape[1:], model)
    elif keys == ["emb"]:
        v = W.table_rows(key, "embed", padded_vocab, sds.shape[1], model)
    elif keys == ["unemb"]:
        v = W.table_rows(key, "unembed", padded_vocab, sds.shape[0],
                         model).T
    elif keys[0] == "final_norm":
        v = W.top_leaf(key, f"final_norm.{keys[1]}", sds.shape, model)
    else:
        raise KeyError(f"no checkpoint name for program leaf {keys}")
    return v.astype(sds.dtype)


def param_builder(cfg, config: dict):
    """``build(key)``: the program's quantized parameter tree from the
    checkpoint of ``key``, quantized by the program's own load path."""
    from repro.core.wquant import quantize_lm_weights
    from repro.models import init_lm, lm_param_specs

    shapes = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    model = dict(config, head_dim=cfg.head_dim)

    def build(key):
        tree = jax.tree_util.tree_map_with_path(
            lambda p, s: _leaf_values(key, p, s, model, cfg.padded_vocab),
            shapes)
        if cfg.weight_quant == "int8":
            tree = quantize_lm_weights(tree, cfg, lm_param_specs(cfg))
        return tree

    return build


def make_params(cfg, config: dict, seed: int, mesh):
    """The parameters of ``seed``: one jitted call on the device, placed
    as the program places its parameters."""
    from repro.launch.steps import param_shardings

    with mesh:
        return jax.jit(param_builder(cfg, config),
                       out_shardings=param_shardings(cfg, mesh))(
            W.base_key(seed))


def make_engine(cfg, params, mesh, mix: dict):
    from repro.serving import ServeEngine

    return ServeEngine(cfg, params, mesh, num_slots=mix["slots"],
                       max_len=mix["max_len"],
                       prefill_len=mix["prefill_len"])
