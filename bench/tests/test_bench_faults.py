"""A run of a small cell on the CPU, with the look for a chip skipped:
sound, it comes out correct; with a token altered where the decode step
produces it, or with the admission's insert returning the cache
unchanged, ``correct`` comes out false. Without a TPU, and without the
system under test beside it, the command exits non-zero and prints no
result."""
import json
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, spec

DATA = spec.BENCH / "tests" / "data"
SEED = 2**31 + 4242


def _cell(monkeypatch, name):
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(spec, "peaks", lambda kind: {
        "flops": {"fp8_e4m3": 1e12}, "hbm_bytes_per_s": 1e11})
    bench = json.load(open(DATA / "bench.json"))
    return spec.Cell(bench, name, data_dir=DATA)


@pytest.fixture
def tiny_cell(monkeypatch):
    return _cell(monkeypatch, "tiny_open")


def _run(cell, trace=False):
    return harness.run_cell(cell, SEED, 1.5, trace, time.perf_counter())


@pytest.mark.parametrize("name,reported", [
    ("tiny_open", {"ttft_p90_ms", "itl_p50_ms", "itl_p95_ms", "setup_s"}),
    ("tiny_sat", {"itl_p50_ms", "itl_p95_ms", "output_tok_s", "setup_s"})])
def test_sound_run_is_correct(monkeypatch, name, reported):
    # an open loop from the start, and one above the knee whose window
    # opens once every slot is full
    res = _run(_cell(monkeypatch, name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == reported
    assert list(res)[-1] == "checks"


def test_altered_token_is_caught(tiny_cell, monkeypatch):
    from repro.serving.engine import ServeEngine

    vocab = tiny_cell.config["vocab_size"]
    decode = ServeEngine._dispatch_decode

    def altered(self):
        tok, mid, caches = decode(self)
        return (tok + 1) % vocab, mid, caches

    monkeypatch.setattr(ServeEngine, "_dispatch_decode", altered)
    res = _run(tiny_cell)
    assert not res["correct"]
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_unchanged_cache_is_caught(tiny_cell, monkeypatch):
    from repro.serving.engine import ServeEngine

    build = ServeEngine._bind_rung

    def bind(self, i):
        build(self, i)
        self._insert = lambda caches, kv, slot: caches

    monkeypatch.setattr(ServeEngine, "_bind_rung", bind)
    res = _run(tiny_cell)
    assert not res["correct"]
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_traced_run_reports_layer_metrics(tiny_cell):
    res = _run(tiny_cell, trace=True)
    assert res["correct"]
    assert {"engine.queue_wait_p90_ms", "prefill.admit_ms_p50",
            "decode.step_ms_p50", "decode.mfu_pct",
            "prefill.mfu_pct"} <= set(res["metrics"])
    assert "window_s" in res["device"] and "breakdown" in res


def test_no_chip_no_result():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2_code",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert not r.stdout.strip()
