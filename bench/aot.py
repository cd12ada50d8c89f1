"""Compile a cell's programs for a described TPU v5e, without the chip,
and print each program's ``memory_analysis`` (bytes on each chip): the
weight init, prefill, insert and decode at the cell's sizes, on the
cell's mesh (``harness.cell_mesh``) over the described chips. What the
chip's compiler refuses, or a program that does not fit, shows here at
no chip time.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell>
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from bench import harness, serve, spec
    from repro.distributed import sharding as shd
    from repro.launch import shapes as shp
    from repro.launch.steps import jit_serve_step, param_shardings
    from repro.serving import engine as eng
    from repro.serving.cache import make_insert_fn

    jax.config.update("jax_enable_compilation_cache", False)
    # the program asks the default backend whether to interpret its
    # kernels; the described chip is a TPU
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    mesh = harness.cell_mesh(cell, topo.devices)
    whole = NamedSharding(mesh, PartitionSpec())   # replicated
    mix = cell.traffic
    cfg = serve.model_config(cell.config)

    def sds(tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    def report(name, compiled):
        m = compiled.memory_analysis()
        print(json.dumps({
            "cell": cell.name, "program": name,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "code_bytes": m.generated_code_size_in_bytes,
            "kernels": compiled.as_text().count("tpu_custom_call")}),
            flush=True)

    with mesh:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
        ps = param_shardings(cfg, mesh)
        build = serve.param_builder(cfg, cell.config)
        c = jax.jit(build, out_shardings=ps).lower(key).compile()
        report("init", c)
        params = sds(jax.eval_shape(build, key), ps)
        decode, (_, cs, tok_s) = jit_serve_step(
            cfg, mix["slots"], mix["max_len"], mesh, donate=True,
            per_slot=True)
        caches = sds(shp.cache_specs(cfg, mix["slots"], mix["max_len"]), cs)
        i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                 sharding=whole)

        def rules(fn):
            def wrapped(*a):
                with shd.sharding_rules(mesh):
                    return fn(*a)
            return wrapped

        prefill = jax.jit(rules(eng._make_prefill_fn(cfg)))
        c = prefill.lower(params, {"tokens": i32((1, mix["prefill_len"]))},
                          i32(())).compile()
        report("prefill", c)
        _, kv = jax.eval_shape(prefill, params,
                               {"tokens": i32((1, mix["prefill_len"]))},
                               i32(()))
        insert = jax.jit(rules(make_insert_fn(cfg)), donate_argnums=(0,))
        report("insert", insert.lower(
            caches, sds(kv, jax.tree.map(lambda _: whole, kv)),
            i32(())).compile())
        report("decode", decode.lower(params, caches, i32((mix["slots"], 1)),
                                      i32((mix["slots"],))).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
