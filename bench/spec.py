"""Find a cell's files by name: the benchmark description, the
configuration, the traffic mix, the cell's check and each metric's
reader. Nothing here imports JAX or the program."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Optional[Path] = None) -> dict:
    return _json(path or ROOT / "BENCHMARK.json")


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT,
                 data_dir: Path = BENCH):
        self.bench = bench
        self.workload = _named(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = _named(bench["configs"], self.workload["config"], "config")
        self.config = _json(root / entry["file"])
        self.traffic = _json(data_dir / "traffic"
                             / f"{self.workload['traffic']}.json")
        self.check = _json(data_dir / "cells" / f"{name}.json")

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics this cell reports: its end-to-end metrics with
        ``--trace 0``, its per-layer metrics with ``--trace 1``. A metric
        without a ``workloads`` key belongs to every cell."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def load_reader(metric: str) -> Callable:
    """``metrics/<metric>.py``'s ``read(run)``: the metric's value, or
    None where the run holds nothing for it to read."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(kind: str):
    """The plain reference module a configuration names."""
    path = BENCH / "reference" / f"{kind}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; a device that is not in the table
    is an error, not a default."""
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table['devices'])})")
    return table["devices"][device_kind]
