"""The correctness check's control at a small size: the plain reference
computed in int4 in the program's place reads above the limit, while the
program's served tokens read below it."""
import json

import jax
import numpy as np

from bench import check, control, loop, serve, spec, traffic

DATA = spec.BENCH / "tests" / "data"


def test_int4_control_fails_where_the_program_passes():
    bench = json.load(open(DATA / "bench.json"))
    cell = spec.Cell(bench, "tiny_open", data_dir=DATA)
    from repro.launch.mesh import make_local_mesh

    seed, mix = 77, cell.traffic
    cfg = serve.model_config(cell.config)
    mesh = make_local_mesh(1, jax.devices()[:1])
    engine = serve.make_engine(
        cfg, serve.make_params(cfg, cell.config, seed, mesh), mesh, mix)
    engine.warmup()
    loop.warm(engine)
    log = loop.drive(engine, traffic.generate(mix, 512, seed, 1.5), 1.5,
                     mix["window"], drain_limit_s=30)
    blk = check.block(mix)
    picked = check.sample(log.records, blk[0], seed)
    assert picked
    prog, ctrl = control.control_gaps(cell.config, seed, picked, blk)
    limit = cell.check["logit_gap_limit"]
    assert prog.max() <= limit < ctrl.max()
    # the check's own number agrees with the control's program reading
    np.testing.assert_allclose(
        check.served_gaps(cell.config, seed, picked, blk), prog, atol=1e-5)
