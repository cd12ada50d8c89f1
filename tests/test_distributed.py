"""Distribution: sharding resolver, multi-device pjit equivalence, the
int8 ring all-reduce, and a miniature multi-pod dry-run -- all on fake
host devices in subprocesses (the main process keeps 1 device)."""
import numpy as np
import pytest

from repro.distributed.sharding import DEFAULT_RULES


def test_resolver_divisibility_guard():
    import jax
    from repro.distributed.sharding import make_resolver
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    one = make_resolver(mesh)
    s = one(("batch", None), (4, 8))
    assert s.spec == jax.sharding.PartitionSpec(None, None) or True
    # dims not divisible by the axis drop the constraint instead of failing
    s2 = one(("vocab",), (51865,))
    assert s2 is not None


def test_default_rules_cover_model_axes():
    for ax in ("batch", "fsdp", "heads", "kv", "dff", "vocab", "experts"):
        assert ax in DEFAULT_RULES


def test_sharded_train_step_matches_single_device(subproc):
    """pjit on a 4-device (2,2) mesh computes the same loss as 1 device."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.shapes import ShapeSpec, make_batch
from repro.launch.steps import jit_train_step, param_shardings
from repro.models import init_lm, lm_loss
from repro.optim import OptConfig, init_opt_state

cfg = get_config("llama3_8b").scaled_down()
shape = ShapeSpec("t", "train", 32, 4)
batch = make_batch(cfg, shape)
params = init_lm(jax.random.PRNGKey(0), cfg)
loss_1dev, _ = lm_loss(cfg, params, batch)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
opt = OptConfig(lr=1e-3)
step, (ps, os_, bs) = jit_train_step(cfg, opt, shape, mesh, donate=False)
params_s = jax.device_put(params, ps)
opt_state = jax.device_put(init_opt_state(params, opt), os_)
batch_s = {k: jax.device_put(np.asarray(v), bs[k]) for k, v in batch.items()}
_, _, metrics = step(params_s, opt_state, batch_s)
print("LOSSES", float(loss_1dev), float(metrics["loss"]))
err = abs(float(loss_1dev) - float(metrics["loss"]))
assert err < 5e-2, err
""", devices=4)
    assert "LOSSES" in out


def test_int8_ring_all_reduce(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.collectives import int8_ring_all_reduce

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
contribs = jnp.asarray(rng.standard_normal((8, 32, 16)) * 5, jnp.float32)
contribs = jax.device_put(contribs, NamedSharding(mesh, P("data")))
out = int8_ring_all_reduce(contribs, mesh, "data")
want = np.asarray(contribs).sum(0)
got = np.asarray(out)
# every shard row holds the ring sum, within int8 wire precision
for i in range(8):
    rel = np.abs(got[i] - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.05, rel
print("RING_OK", rel)
""", devices=8)
    assert "RING_OK" in out


def test_mini_multipod_dryrun(subproc):
    """A miniature (2,2,2) 'multi-pod' mesh: lower+compile a real arch's
    train step and check collectives span the pod axis."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.shapes import ShapeSpec, batch_specs
from repro.launch.steps import jit_train_step, param_shapes, opt_state_shapes
from repro.optim import OptConfig
from repro.launch.hlo_analysis import analyze_hlo

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_config("mixtral_8x7b").scaled_down()
shape = ShapeSpec("t", "train", 64, 8)
opt = OptConfig()
step, _ = jit_train_step(cfg, opt, shape, mesh)
args = (param_shapes(cfg), opt_state_shapes(cfg, opt), batch_specs(cfg, shape))
compiled = step.lower(*args).compile()
res = analyze_hlo(compiled.as_text())
assert res["collective_total_bytes_per_device"] > 0
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes > 0
print("MINIPOD_OK", res["collective_counts"])
""", devices=8)
    assert "MINIPOD_OK" in out


def test_serve_step_sharded(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.steps import jit_serve_step, param_shardings
from repro.launch.shapes import cache_specs
from repro.models import init_lm

cfg = get_config("llama3_8b").scaled_down()
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
B, T = 4, 64
serve, (ps, cs, ts) = jit_serve_step(cfg, B, T, mesh, donate=False)
params = jax.device_put(init_lm(jax.random.PRNGKey(0), cfg), ps)
caches = jax.tree.map(lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype)), cache_specs(cfg, B, T))
caches = jax.device_put(caches, cs)
toks = jax.device_put(jnp.ones((B, 1), jnp.int32), ts)
new_tok, logits, new_caches = serve(params, caches, toks, jnp.asarray(3, jnp.int32))
assert new_tok.shape == (B, 1)
assert np.isfinite(np.asarray(logits[..., :cfg.vocab_size], np.float32)).all()
print("SERVE_OK")
""", devices=4)
    assert "SERVE_OK" in out


def test_sharded_quant_dot_matches_single_device(subproc):
    """PR 4 acceptance: a 2-device mesh quant_dot (shard_map dispatch,
    per-shard weight scales, mesh axes in the plan cache key) matches the
    single-device output -- bitwise for int8, allclose for fp8."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.api import QuantDotSpec, QuantEpilogue, plan_for, quant_dot
from repro.core.quant import QuantConfig
from repro.core.wquant import quantize_weight
from repro.distributed import sharding as shd

rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
w = jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("model",))

for mode, exact in (("int8", True), ("fp8_e4m3", False)):
    qt = quantize_weight(w, mode)
    ref = quant_dot(x, qt, mode=mode, backend="xla")          # no mesh
    with shd.sharding_rules(mesh):
        spec = QuantDotSpec.for_config(
            256, QuantConfig(mode=mode, rotate="hadamard", backend="xla"),
            weight_axes=(None, "dff"))                        # out dim -> model
        plan = spec.plan(jnp.float32, d=128)
        assert plan.mesh_axes == ("model",), plan.mesh_axes   # in the cache key
        assert plan is not plan_for(256, backend="xla",
                                    epilogue=QuantEpilogue(mode))
        sharded = spec.bind(qt)(x)
    a, b = np.asarray(sharded, np.float32), np.asarray(ref, np.float32)
    if exact:
        assert (a == b).all(), np.abs(a - b).max()            # bitwise int8
    else:
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

# per-shard scales are genuinely used: perturbing the second shard's
# scale slice changes only that shard's output columns
qt = quantize_weight(w, "int8")
sw2 = qt.scale.at[:, 64:].mul(2.0)
with shd.sharding_rules(mesh):
    o1 = quant_dot(x, (qt.q, qt.scale), mode="int8", backend="xla",
                   weight_axes=(None, "dff"))
    o2 = quant_dot(x, (qt.q, sw2), mode="int8", backend="xla",
                   weight_axes=(None, "dff"))
assert (np.asarray(o1[:, :64]) == np.asarray(o2[:, :64])).all()
assert not (np.asarray(o1[:, 64:]) == np.asarray(o2[:, 64:])).all()

# the grouped (non-power-of-2) transform shards too
xg = jnp.asarray(rng.standard_normal((8, 96)), jnp.float32)
wg = quantize_weight(jnp.asarray(rng.standard_normal((96, 64)) * 0.05,
                                 jnp.float32), "int8")
refg = quant_dot(xg, wg, mode="int8", backend="xla")
with shd.sharding_rules(mesh):
    outg = quant_dot(xg, wg, mode="int8", backend="xla",
                     weight_axes=(None, "dff"))
assert (np.asarray(outg) == np.asarray(refg)).all()
print("SHARDED_QD_OK")
""", devices=2)
    assert "SHARDED_QD_OK" in out


def test_serve_step_sharded_prequant_qtensor(subproc):
    """The full serving stack on a (2,2) mesh with pre-quantized QTensor
    weights: QTensor-structured param shardings resolve, the scanned
    forward consumes q/scale shards directly (shard_map inside the layer
    scan), and decode logits stay finite."""
    out = subproc("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.launch.shapes import cache_specs
from repro.launch.steps import jit_serve_step, make_param_init

quant = QuantConfig(mode="int8", rotate="hadamard", backend="xla",
                    kv_quant=True)
cfg = dataclasses.replace(
    get_config("llama3_8b").scaled_down().with_quant(quant),
    weight_quant="int8")
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
B, T = 4, 64
serve, (ps, cs, ts) = jit_serve_step(cfg, B, T, mesh, donate=False)
params = jax.jit(make_param_init(cfg), out_shardings=ps)(
    jax.random.PRNGKey(0))
caches = jax.tree.map(lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype)),
                      cache_specs(cfg, B, T))
caches = jax.device_put(caches, cs)
toks = jax.device_put(jnp.ones((B, 1), jnp.int32), ts)
new_tok, logits, _ = serve(params, caches, toks, jnp.asarray(3, jnp.int32))
assert new_tok.shape == (B, 1)
assert np.isfinite(np.asarray(logits[..., :cfg.vocab_size], np.float32)).all()
print("SERVE_QTENSOR_OK")
""", devices=4)
    assert "SERVE_QTENSOR_OK" in out


def test_tensor_parallel_rotated_down_proj_is_never_gathered(subproc):
    """On a (1, 4) tensor-parallel mesh the rotated down-projection is
    stored by out-channel and its quant_dot runs the fused kernel on
    each device's columns: no device is handed the whole weight."""
    out = subproc("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.core import api
from repro.core.quant import QuantConfig
from repro.kernels.registry import TRACE_COUNTS
from repro.launch.mesh import make_local_mesh
from repro.launch.shapes import cache_specs
from repro.launch.steps import jit_serve_step, make_param_init

from repro.launch.train import scaled_config

quant = QuantConfig(mode="fp8_e4m3", rotate="hadamard", backend="pallas")
cfg = dataclasses.replace(    # d_model 384, d_ff 1024: the kernel fuses
    scaled_config(get_config("phi4-mini-3.8b"), 1 / 64).with_quant(quant),
    weight_quant="int8")
mesh = make_local_mesh(4)
B, T = 4, 32
serve, (ps, cs, ts) = jit_serve_step(cfg, B, T, mesh, donate=False)
wd = ps["groups"][0]["p0"]["mlp"]["w_down"]
assert wd.q.spec == P(None, None, ("data", "model")), wd.q.spec
params = jax.jit(make_param_init(cfg), out_shardings=ps)(
    jax.random.PRNGKey(0))
caches = jax.device_put(jax.tree.map(
    lambda s: jnp.zeros(s.shape, s.dtype), cache_specs(cfg, B, T)), cs)
toks = jax.device_put(jnp.ones((B, 1), jnp.int32), ts)
key = ("sharded_quant_dot", "replicated_operand")
before = TRACE_COUNTS[key]
_, logits, _ = serve(params, caches, toks, jnp.asarray(3, jnp.int32))
assert TRACE_COUNTS[key] == before, TRACE_COUNTS[key]
disp = api._LAST_SHARDED_DISPATCH
assert disp["fused"] and disp["mesh_axes"] == ("data", "model"), disp
assert np.isfinite(np.asarray(logits[..., :cfg.vocab_size], np.float32)).all()
print("TP_DOWN_PROJ_OK")
""", devices=4)
    assert "TP_DOWN_PROJ_OK" in out


def test_sharded_quant_dot_fused_shard_local_2dev(subproc):
    """PR 5 acceptance: on a 2-device mesh the shard-local compute is the
    FUSED rotate-once Pallas kernel (not the unfused xla oracle) with the
    activation row-sharded over the data axes, bitwise-int8 vs the
    single-device kernel, per-shard weight scales preserved."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import api
from repro.core.api import quant_dot
from repro.core.wquant import quantize_weight
from repro.distributed import sharding as shd
from repro.kernels import registry

rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
w = jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32)
qt = quantize_weight(w, "int8")
ref = quant_dot(x, qt, mode="int8", backend="pallas")     # single device
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 2), ("data", "model"))
unfused_before = registry.TRACE_COUNTS[("sharded_quant_dot", "unfused_local")]
kernel_before = registry.TRACE_COUNTS[("pallas", "quant_dot")]
with shd.sharding_rules(mesh):
    out = quant_dot(x, qt, mode="int8", backend="pallas",
                    weight_axes=(None, "dff"))
assert (np.asarray(out) == np.asarray(ref)).all()         # bitwise int8
disp = api._LAST_SHARDED_DISPATCH
assert disp["fused"] and disp["backend"] == "pallas", disp
assert disp["mesh_axes"] == ("model",), disp
assert disp["row_axes"] == ("data",), disp                # row-sharded in_spec
# the fused kernel really traced shard-locally; no unfused fallback count
assert registry.TRACE_COUNTS[("pallas", "quant_dot")] == kernel_before + 1
assert registry.TRACE_COUNTS[("sharded_quant_dot", "unfused_local")] == unfused_before

# per-shard weight scales are genuinely used on the fused path too
sw2 = qt.scale.at[:, 64:].mul(2.0)
with shd.sharding_rules(mesh):
    o1 = quant_dot(x, (qt.q, qt.scale), mode="int8", backend="pallas",
                   weight_axes=(None, "dff"))
    o2 = quant_dot(x, (qt.q, sw2), mode="int8", backend="pallas",
                   weight_axes=(None, "dff"))
assert (np.asarray(o1[:, :64]) == np.asarray(o2[:, :64])).all()
assert not (np.asarray(o1[:, 64:]) == np.asarray(o2[:, 64:])).all()
print("FUSED_SHARD_LOCAL_OK")
""", devices=2)
    assert "FUSED_SHARD_LOCAL_OK" in out


def test_sharded_quant_dot_row_sharded_4dev(subproc):
    """(2,2) mesh: rows genuinely split over the data axis (2 shards x 8
    rows) while the weight splits over model -- each device rotates only
    its rows and the assembled output is bitwise the single-device int8
    result. Rows not divisible by the data axis drop the row constraint
    (divisibility guard) but still compute correctly."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import api
from repro.core.api import quant_dot
from repro.core.wquant import quantize_weight
from repro.distributed import sharding as shd

rng = np.random.default_rng(1)
w = jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32)
qt = quantize_weight(w, "int8")
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
for rows, want_axes in ((16, ("data",)), (9, ())):
    x = jnp.asarray(rng.standard_normal((rows, 256)), jnp.float32)
    ref = quant_dot(x, qt, mode="int8", backend="pallas")
    with shd.sharding_rules(mesh):
        out = quant_dot(x, qt, mode="int8", backend="pallas",
                        weight_axes=(None, "dff"))
    assert (np.asarray(out) == np.asarray(ref)).all(), rows
    assert api._LAST_SHARDED_DISPATCH["row_axes"] == want_axes, (
        rows, api._LAST_SHARDED_DISPATCH)
print("ROW_SHARDED_OK")
""", devices=4)
    assert "ROW_SHARDED_OK" in out


def test_sharded_quant_dot_fallbacks_are_observable(subproc):
    """Satellite: a mesh plan silently losing the sharded/fused hot path
    warns once per process per reason and bumps a TRACE_COUNTS counter
    every time -- both for unfused shard-local compute (xla backend) and
    for a plan whose mesh axes the current mesh does not provide."""
    out = subproc("""
import warnings
import jax, jax.numpy as jnp, numpy as np
from repro.core.api import QuantEpilogue, plan_for, quant_dot
from repro.core.wquant import quantize_weight
from repro.distributed import sharding as shd
from repro.kernels import registry

rng = np.random.default_rng(2)
x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
qt = quantize_weight(
    jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32), "int8")
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("model",))

key_u = ("sharded_quant_dot", "unfused_local")
with warnings.catch_warnings(record=True) as wl:
    warnings.simplefilter("always")
    before = registry.TRACE_COUNTS[key_u]
    with shd.sharding_rules(mesh):
        quant_dot(x, qt, mode="int8", backend="xla", weight_axes=(None, "dff"))
        quant_dot(x.astype(jnp.float32) * 2, qt, mode="int8", backend="xla",
                  weight_axes=(None, "dff"))
# counted at every dispatch (eager calls dispatch per call; under jit,
# once per trace) -- but WARNED only once
assert registry.TRACE_COUNTS[key_u] == before + 2
msgs = [str(v.message) for v in wl if "unfused_local" in str(v.message)]
assert len(msgs) == 1 and "xla" in msgs[0], msgs   # warn-once

key_m = ("sharded_quant_dot", "mesh_mismatch")
plan = plan_for(256, backend="pallas", epilogue=QuantEpilogue("int8"),
                mesh_axes=("model",))
ref = quant_dot(x, qt, mode="int8", backend="pallas")
with warnings.catch_warnings(record=True) as wl:
    warnings.simplefilter("always")
    before = registry.TRACE_COUNTS[key_m]
    out = quant_dot(x, (qt.q, qt.scale), plan)     # no active mesh
assert registry.TRACE_COUNTS[key_m] == before + 1
assert any("mesh_mismatch" in str(v.message) for v in wl)
assert (np.asarray(out) == np.asarray(ref)).all()  # fallback is correct

# per-tensor scales can't shard_map: the mesh plan must record the
# unshardable site instead of silently running replicated
key_s = ("sharded_quant_dot", "unshardable_site")
plan_pt = plan_for(256, backend="xla",
                   epilogue=QuantEpilogue("int8", per_token=False),
                   mesh_axes=("model",))
with warnings.catch_warnings(record=True) as wl:
    warnings.simplefilter("always")
    before = registry.TRACE_COUNTS[key_s]
    with shd.sharding_rules(mesh):
        outp = quant_dot(x, (qt.q, qt.scale), plan_pt)
assert registry.TRACE_COUNTS[key_s] == before + 1
assert any("unshardable_site" in str(v.message) for v in wl)
assert np.isfinite(np.asarray(outp, np.float32)).all()
print("FALLBACK_OBSERVABLE_OK")
""", devices=2)
    assert "FALLBACK_OBSERVABLE_OK" in out


def test_sharded_quant_dot_in_main_process():
    """Main-process multi-device coverage (the CI tier1-multidevice job:
    XLA_FLAGS device_count=2 on the pytest process itself, no subprocess
    indirection): skipped on single-device runs."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices in the main process "
                    "(tier1-multidevice CI job)")
    import jax.numpy as jnp
    from repro.core.api import quant_dot
    from repro.core.wquant import quantize_weight
    from repro.distributed import sharding as shd

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 64)) * 0.05, jnp.float32)
    qt = quantize_weight(w, "int8")
    ref = quant_dot(x, qt, mode="int8", backend="xla")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("model",))
    with shd.sharding_rules(mesh):
        out = quant_dot(x, qt, mode="int8", backend="xla",
                        weight_axes=(None, "dff"))
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_pallas_kernels_run_under_shard_map_on_a_mesh(subproc):
    """Mosaic kernels cannot be partitioned by GSPMD: under a multi-device
    mesh every Pallas call -- the fused QK rotate+quantize and a
    quant_dot whose weight is not sharded by out-channel -- runs inside a
    shard_map over the rows, with results bitwise the single-device
    ones. The quant_dot's whole weight on every device is counted."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.api import QuantEpilogue, hadamard, plan_for, quant_dot
from repro.core.wquant import quantize_weight
from repro.distributed import sharding as shd
from repro.kernels.registry import TRACE_COUNTS
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(3)
mesh = make_mesh((1, 2), ("data", "model"))
q = jnp.asarray(rng.standard_normal((4, 1, 6, 128)), jnp.float32)
plan = plan_for(128, backend="pallas",
                epilogue=QuantEpilogue("fp8_e4m3", dequant=True))
x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
qt = quantize_weight(
    jnp.asarray(rng.standard_normal((256, 64)) * 0.05, jnp.float32), "int8")

def run(q, x):
    return hadamard(q, plan), quant_dot(x, qt, mode="int8", backend="pallas")

def on_mesh(q, x):
    with shd.sharding_rules(mesh):
        return run(q, x)

want = jax.jit(run)(q, x)
key = ("sharded_quant_dot", "replicated_operand")
before = TRACE_COUNTS[key]
jaxpr = str(jax.make_jaxpr(on_mesh)(q, x))
assert jaxpr.count("shard_map") == 2, jaxpr.count("shard_map")
assert TRACE_COUNTS[key] == before + 1     # the quant_dot, not the QK site
got = jax.jit(on_mesh)(q, x)
for g, w in zip(got, want):
    assert g.shape == w.shape
    assert (np.asarray(g) == np.asarray(w)).all()
print("SHARD_MAP_KERNELS_OK")
""", devices=2)
    assert "SHARD_MAP_KERNELS_OK" in out
