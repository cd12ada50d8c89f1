"""The cell's mesh, and what a run counts as its own: every cell gets
the mesh of its own ``chips``, a one-chip cell (1, 1) on its one device,
a four-chip cell (1, 4) over four devices (four forced host devices, in
a child process, since JAX fixes the device count at its start), and a
tiny cell runs whole on them. Counter ticks from before a run are not
the run's."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _four_devices(code: str) -> str:
    """Run ``code`` in a child with four host devices; its stdout."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(spec.ROOT / "src"),
                                          str(spec.ROOT)]),
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", str(spec.ROOT)),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=spec.ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def check_cell_mesh(bench, name, data_dir=spec.BENCH):
    """The cell's mesh is (data 1, model chips) over its first chips
    devices; a four-chip cell's is built in a child with four devices."""
    cell = spec.Cell(bench, name, data_dir=data_dir)
    if cell.chips == 1:
        mesh = harness.cell_mesh(cell, jax.devices())
        assert dict(mesh.shape) == {"data": 1, "model": 1}
        assert list(mesh.devices.flat) == jax.devices()[:1]
        return
    assert cell.chips == 4
    out = _four_devices(f"""
import json, jax
from pathlib import Path
from bench import harness, spec
cell = spec.Cell(json.loads({json.dumps(json.dumps(bench))}), {name!r},
                 data_dir=Path({str(data_dir)!r}))
mesh = harness.cell_mesh(cell, jax.devices())
print(json.dumps([dict(mesh.shape), [d.id for d in mesh.devices.flat]]))
""")
    shape, ids = json.loads(out.splitlines()[-1])
    assert shape == {"data": 1, "model": 4} and ids == [0, 1, 2, 3]


@pytest.mark.parametrize("name", CELLS)
def test_cell_gets_the_mesh_of_its_chips(name):
    check_cell_mesh(spec.load_benchmark(), name)


def test_four_chip_cell_splits_the_model_four_ways():
    bench = spec.load_benchmark()
    bench["workloads"][0]["chips"] = 4
    check_cell_mesh(bench, bench["workloads"][0]["name"])


def test_tiny_cell_runs_whole_on_four_devices():
    out = _four_devices("""
import json, time, jax
from bench import harness, spec
harness.require_chips = lambda chips: jax.devices()[:chips]
spec.peaks = lambda kind: {"flops": {"fp8_e4m3": 1e12},
                           "hbm_bytes_per_s": 1e11}
bench = json.load(open(spec.BENCH / "tests" / "data" / "bench.json"))
bench["workloads"][0]["chips"] = 4
cell = spec.Cell(bench, "tiny_open", data_dir=spec.BENCH / "tests" / "data")
# a KV head per chip, and the kernels the chip cells run sharded
cell.config["num_key_value_heads"] = 4
cell.config["program"]["quant"]["backend"] = "pallas"
res = harness.run_cell(cell, 2**31 + 4242, 1.5, True, time.perf_counter())
print(json.dumps(res))
""")
    res = json.loads(out.splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    for name in ("decode.mfu_pct", "prefill.mfu_pct"):
        assert 0 < res["metrics"][name]["value"] <= 100


def test_ticks_from_before_the_run_are_not_its_own(monkeypatch):
    from repro.kernels.registry import TRACE_COUNTS

    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "flops": {"fp8_e4m3": 1e12}, "hbm_bytes_per_s": 1e11})
    data = spec.BENCH / "tests" / "data"
    cell = spec.Cell(json.load(open(data / "bench.json")), "tiny_open",
                     data_dir=data)
    key = ("quant_dot", "vmem_unfused")
    monkeypatch.setitem(TRACE_COUNTS, key, TRACE_COUNTS[key] + 1)
    res = harness.run_cell(cell, 2**31 + 4242, 1.5, False,
                           time.perf_counter())
    assert res["checks"]["kernel_fallbacks"]["value"] == 0
    assert res["correct"], res["checks"]
