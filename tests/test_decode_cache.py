"""The decode layer scan reads the stacked KV caches in place: no scan
slices an attention cache out as xs or stacks one back as ys, the only
writes to a stack land one row per layer of one slot, and the step is
bitwise the xs/ys decode it replaced (``tests/xs_decode.py``), across
attention, expert, mixed scan-state and cross-attention stacks, at
per-slot and shared positions."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_utils import iter_eqns, scan_xs_ys
from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.kernels.registry import TRACE_COUNTS
from repro.launch import shapes as shp
from repro.models import init_lm, lm_decode_step
from repro.models.lm import kv_stacks
from xs_decode import xs_decode_step

SLOTS, MAX_LEN = 4, 24

# (attention sites read in place, state leaves scanned as xs/ys)
ARCHS = {"llama3_8b": (1, 0), "mixtral_8x7b": (1, 0),
         "zamba2_7b": (1, 18), "whisper_base": (1, 0)}

# per-slot positions over four steps; slot 1 retires after step 2 and is
# reused from row 0 while the others go on
PER_SLOT = [[3, 7, 0, 12], [4, 8, 1, 13], [5, 0, 2, 14], [6, 1, 3, 15]]
SHARED = [5, 6, 7, 8]


@functools.lru_cache(maxsize=None)
def _setup(arch):
    """A scaled config on the fp8 rotated path, its params, and caches
    filled with noise so every row the attention reads matters. The KV
    cache is fp8 but for whisper, whose cross-attention reads its
    cached K/V in the model dtype."""
    cfg = get_config(arch).scaled_down().with_quant(QuantConfig(
        mode="fp8_e4m3", rotate="hadamard", backend="xla",
        kv_quant=arch != "whisper_base"))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    specs = shp.cache_specs(cfg, SLOTS, MAX_LEN)
    leaves, tree = jax.tree.flatten(specs)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    caches = jax.tree.unflatten(tree, [
        jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
        for k, s in zip(keys, leaves)])
    return cfg, params, caches


def _positions(mode, step):
    if mode == "per_slot":
        return jnp.asarray(PER_SLOT[step], jnp.int32)
    return jnp.asarray(SHARED[step], jnp.int32)


def _tokens(cfg, step):
    return jax.random.randint(jax.random.PRNGKey(100 + step), (SLOTS, 1), 0,
                              cfg.vocab_size, jnp.int32)


@pytest.mark.parametrize("mode", ["per_slot", "shared"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_scan_carries_the_kv_stacks(arch, mode):
    cfg, params, caches = _setup(arch)
    kv = {tuple(a.shape) for a in kv_stacks(cfg, caches)}
    before = dict(TRACE_COUNTS)
    jaxpr = jax.make_jaxpr(functools.partial(lm_decode_step, cfg))(
        params, caches, _tokens(cfg, 0), _positions(mode, 0))
    ticks = tuple(TRACE_COUNTS[("decode_cache", k)]
                  - before.get(("decode_cache", k), 0)
                  for k in ("in_place", "scanned"))
    assert ticks == ARCHS[arch]

    scanned = [(role, tuple(a.shape)) for role, a in scan_xs_ys(jaxpr)
               if tuple(a.shape) in kv]
    assert scanned == [], f"KV stacks scanned as xs/ys: {scanned}"

    KH, hd = cfg.num_kv_heads, cfg.head_dim
    writes = 0
    for e in iter_eqns(jaxpr):
        name = e.primitive.name
        if name not in ("dynamic_update_slice", "scatter", "scatter-add"):
            continue
        stack = tuple(e.invars[0].aval.shape)
        if stack not in kv:
            continue
        # one row of one slot in every layer of the stack
        assert name == "dynamic_update_slice", name
        assert e.invars[1].aval.shape == (stack[0], 1, 1, KH, hd), e.invars[1].aval
        writes += 1
    # one write a slot into the K and the V stack of each site
    assert writes == 2 * SLOTS * ARCHS[arch][0]


@pytest.mark.parametrize("mode", ["per_slot", "shared"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_carried_decode_bitwise_matches_xs_decode(arch, mode):
    cfg, params, caches = _setup(arch)
    new = jax.jit(functools.partial(lm_decode_step, cfg))
    old = jax.jit(functools.partial(xs_decode_step, cfg))
    c_new = c_old = caches
    for step in range(len(SHARED)):
        tok, pos = _tokens(cfg, step), _positions(mode, step)
        l_new, c_new = new(params, c_new, tok, pos)
        l_old, c_old = old(params, c_old, tok, pos)
        assert np.array_equal(np.asarray(l_new), np.asarray(l_old)), step
        assert jax.tree.structure(c_new) == jax.tree.structure(c_old)
        for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)),
                                  equal_nan=True), step
