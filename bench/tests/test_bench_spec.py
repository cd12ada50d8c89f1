"""Every cell of BENCHMARK.json resolves its configuration, traffic,
check and metric files by name, and the file keeps to the benchmark's
contract on names, keys and bounds."""
import json
import re

import pytest

from bench import spec, traffic

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = spec.Cell(BENCH, name)
    assert cell.config["name"] == cell.workload["config"]
    assert float(cell.check["logit_gap_limit"]) > 0
    mix = cell.traffic
    assert mix["prompt"]["max"] <= mix["prefill_len"] <= mix["max_len"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]
    for trace in (False, True):
        for m in cell.metrics(trace):
            assert callable(spec.load_reader(m["name"]))
    spec.load_reference(cell.config["reference"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(name):
    cell = spec.Cell(BENCH, name)
    e2e = {m["name"] for m in cell.metrics(False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.metrics(True)
    assert layer
    for m in layer:     # each moves an end-to-end metric the cell reports
        assert m["moves"] in e2e


def test_names_keys_and_bounds_keep_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert json.load(open(spec.ROOT / c["file"]))["reduced"] \
            == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 for x in layers)


@pytest.mark.parametrize("name", CELLS)
def test_a_full_check_fits_its_time_with_every_cell(name):
    # 2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s of compile
    # per cell and 1200 s spare within 43200 s, at 24 cells
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    mix = spec.Cell(BENCH, name).traffic
    assert traffic.request_count(mix, rs) > 10
