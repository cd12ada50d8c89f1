"""Quantized weight storage: the QTensor pytree node (Perf C4/C4', PR 4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.core.wquant import (
    QTensor,
    dequant_tree,
    is_qleaf,
    quantize_lm_weights,
    quantize_weight,
)
from repro.launch.shapes import ShapeSpec, make_batch
from repro.models import init_lm, lm_loss, lm_param_specs
from repro.models.lm import pad_kv_caches, lm_prefill, lm_decode_step


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((512, 384)) * 0.05, jnp.bfloat16)
    q = quantize_lm_weights({"groups": [{"p0": {"attn": {"wq": w}}}]})
    leaf = q["groups"][0]["p0"]["attn"]["wq"]
    assert is_qleaf(leaf) and leaf.q.dtype == jnp.int8 and leaf.mode == "int8"
    back = dequant_tree(leaf, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(w, np.float32)).max()
    assert err < float(jnp.abs(w.astype(jnp.float32)).max()) / 100


def test_small_leaves_not_quantized():
    p = {"norm1": {"scale": jnp.ones((512,))},
         "bias": jnp.zeros((128,)),
         "big": jnp.ones((512, 512), jnp.bfloat16)}
    q = quantize_lm_weights(p)
    assert not is_qleaf(q["norm1"]["scale"]) and not is_qleaf(q["bias"])
    assert is_qleaf(q["big"])


def test_qtensor_is_a_pytree_node():
    """q/scale are children (jit/scan/device_put see through the node);
    mode/axes are static aux data; legacy (q, scale) unpack works."""
    qt = quantize_weight(jnp.ones((64, 32)) * 0.5, "fp8_e4m3",
                         axes=("dff", "fsdp"))
    leaves, treedef = jax.tree.flatten(qt)
    assert [l.shape for l in leaves] == [(64, 32), (1, 32)]
    back = jax.tree.unflatten(treedef, leaves)
    assert back.mode == "fp8_e4m3" and back.axes == ("dff", "fsdp")
    out = jax.jit(lambda t: t.dequant(jnp.float32))(qt)
    assert out.shape == (64, 32)
    q, s = qt  # legacy tuple unpack
    assert q is qt.q and s is qt.scale
    # scan slices both children together (the layer-stacked form)
    stacked = QTensor(q=jnp.zeros((3, 8, 4), jnp.int8),
                      scale=jnp.ones((3, 1, 4)), mode="int8")
    _, sliced = jax.lax.scan(lambda c, t: (c, t.dequant(jnp.float32)),
                             0, stacked)
    assert sliced.shape == (3, 8, 4)


def test_consumer_leaves_stored_in_serving_mode():
    """With a rotating+quantizing config, down-proj weights (the
    quant_dot consumers) store in cfg.quant.mode regardless of size;
    everything else stores int8."""
    quant = QuantConfig(mode="fp8_e4m3", rotate="hadamard", backend="xla")
    cfg = dataclasses.replace(
        get_config("llama3_8b").scaled_down().with_quant(quant),
        weight_quant="int8")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    qp = quantize_lm_weights(params, cfg, lm_param_specs(cfg))
    wd = qp["groups"][0]["p0"]["mlp"]["w_down"]
    assert is_qleaf(wd) and wd.mode == "fp8_e4m3"
    assert wd.q.dtype == jnp.float8_e4m3fn
    # attached from specs: a rotated consumer is stored by out-channel
    assert wd.axes == ("layers", None, "qdout")
    emb = qp["emb"]
    assert is_qleaf(emb) and emb.mode == "int8"


@pytest.mark.parametrize("arch", ["llama3_8b", "mixtral_8x7b", "rwkv6_7b"])
def test_int8_weights_model_close(arch):
    cfg0 = get_config(arch).scaled_down()
    cfg = dataclasses.replace(cfg0, weight_quant="int8")
    batch = make_batch(cfg0, ShapeSpec("t", "train", 32, 2))
    params = init_lm(jax.random.PRNGKey(0), cfg0)
    l0, _ = lm_loss(cfg0, params, batch)
    l1, _ = lm_loss(cfg, quantize_lm_weights(params), batch)
    assert abs(float(l0) - float(l1)) < 0.25, (float(l0), float(l1))


def test_int8_weights_decode_path():
    cfg0 = get_config("llama3_8b").scaled_down()
    cfg = dataclasses.replace(cfg0, weight_quant="int8")
    batch = make_batch(cfg0, ShapeSpec("t", "train", 32, 2))
    qparams = quantize_lm_weights(init_lm(jax.random.PRNGKey(0), cfg0))
    logits, caches = lm_prefill(cfg, qparams, batch)
    caches = pad_kv_caches(cfg, caches, 40)
    tok = jnp.argmax(logits[..., :cfg.vocab_size], -1).astype(jnp.int32)
    lg, _ = lm_decode_step(cfg, qparams, caches, tok, jnp.asarray(32, jnp.int32))
    assert np.isfinite(np.asarray(lg[..., :cfg.vocab_size], np.float32)).all()
