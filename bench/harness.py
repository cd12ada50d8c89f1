"""One run of one cell: device check, compile cache, weights from the
seed, the engine at the cell's sizes, warm-up, the open loop for
``seconds``, then the correctness comparison and the metrics the cell
reports. ``run.py`` is the command; this module is what it calls."""
from __future__ import annotations

import gc
import glob
import os
import resource
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec

# counters of a kernel quietly replaced by a slower path; all must stay 0
FALLBACK_KEYS = (("quant_dot", "vmem_unfused"),
                 ("quant_dot", "stream_fallback"))
FALLBACK_KINDS = ("backend_fallback", "sharded_quant_dot")
# the program's kernel families, by their trace-time counter
KERNEL_COUNTERS = {"quant_dot": ("pallas", "quant_dot"),
                   "transform": ("pallas", "transform"),
                   "fused_dequant": ("pallas", "fused_dequant")}


class NoChip(Exception):
    pass


class CompileClock:
    """Sums the time JAX spends in backend compiles (a persistent-cache
    read is timed as the compile it replaces) and counts the persistent
    cache's hits and the entries it writes, through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.programs, self.hits, self.writes = 0.0, 0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":   # a write
            self.writes += 1


class GcPauses:
    """Every cyclic garbage collection, timed through ``gc.callbacks``:
    (host time at its start, seconds, generation)."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._start = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((self._start,
                                time.perf_counter() - self._start,
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._event)


def _host_report(log, gcs: "GcPauses", usage) -> str:
    """Where the host's time in the window went, to find a stall: the
    collections, the longest admission and decode step against their
    medians, and the process's context switches and CPU time."""
    inside = [p for p in gcs.pauses if log.open <= p[0] < log.close]
    longest = max(inside, key=lambda p: p[1], default=(0.0, 0.0, None))

    def steps(entries):
        d = [b - a for a, b, _ in entries if log.open <= a < log.close]
        return (f"{max(d):.4f} s (median {percentile(d, 50):.4f} s)"
                if d else "none")

    (u0, s0, v0, i0), (u1, s1, v1, i1) = usage
    return (f"host in the window: {len(inside)} collections, "
            f"{sum(p[1] for p in inside):.4f} s, longest {longest[1]:.4f} s "
            f"(generation {longest[2]}); longest admission "
            f"{steps(log.admits)}, longest decode step {steps(log.decodes)};"
            f" context switches {v1 - v0} voluntary, {i1 - i0} involuntary;"
            f" CPU {u1 - u0:.3f} s user, {s1 - s0:.3f} s system")


def _usage():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (r.ru_utime, r.ru_stime, r.ru_nvcsw, r.ru_nivcsw)


def require_chips(chips: int):
    """The devices of a run: a TPU with at least ``chips`` chips, or
    ``NoChip``. The benchmark never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


class Run:
    """What a metric reader sees: the cell, the open loop's log, the
    set-up time, the trace's reduction (traced runs), one chip's peaks,
    the number of chips and the configuration's work counts."""

    def __init__(self, cell, log, setup_s, trace, peaks, seconds):
        self.cell, self.log, self.setup_s = cell, log, setup_s
        self.trace, self.peaks, self.seconds = trace, peaks, seconds
        self.config, self.mix = cell.config, cell.traffic
        self.chips = cell.chips

    def in_window(self, t: float) -> bool:
        return self.log.open <= t < self.log.close

    def due_in_window(self) -> List:
        return [r for r in self.log.records.values() if self.in_window(r.due)]

    def first_token_waits_s(self) -> List[float]:
        """Due time to first token of every request due in the window;
        one never served counts its wait to the end of the run."""
        return [(r.token_times[0] if r.token_times else self.log.end) - r.due
                for r in self.due_in_window()]

    def queue_waits_s(self) -> List[float]:
        return [(r.admit[0] if r.admit else self.log.end) - r.due
                for r in self.due_in_window()]

    def token_gaps_s(self) -> List[float]:
        """Every gap between consecutive tokens of a request that ends
        in the window."""
        out = []
        for r in self.log.records.values():
            t = r.token_times
            out += [b - a for a, b in zip(t, t[1:]) if self.in_window(b)]
        return out

    def tokens_in_window(self) -> int:
        return sum(self.in_window(t) for r in self.log.records.values()
                   for t in r.token_times)

    def admits(self) -> List[tuple]:
        return [a for a in self.log.admits if self.in_window(a[0])]

    def decodes(self) -> List[tuple]:
        return [d for d in self.log.decodes if self.in_window(d[0])]

    def matmul_peak(self) -> float:
        return self.peaks["flops"][self.config["program"]["matmul_dtype"]]


def cell_mesh(cell, devices):
    """The cell's mesh over its first ``cell.chips`` devices: (data 1,
    model chips), the model tensor-parallel over every chip."""
    from repro.launch.mesh import make_local_mesh

    return make_local_mesh(cell.chips, devices[:cell.chips])


def _fallbacks(counts) -> Dict:
    return {"/".join(k): v for k, v in counts.items()
            if (k in FALLBACK_KEYS or k[0] in FALLBACK_KINDS) and v}


def _device_record(devices) -> Dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _start_trace(out_dir: str):
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def _read_trace(out_dir: str):
    from bench.trace import Trace, read_xplane

    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    t = Trace(read_xplane(files[0]))
    shutil.rmtree(out_dir, ignore_errors=True)
    return t


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, out=sys.stdout) -> Dict:
    """One run; returns the result object of the last line."""
    import jax

    from bench import check, loop, serve, traffic
    from repro.kernels.registry import TRACE_COUNTS
    from repro.launch.env import enable_compile_cache

    def say(*a):
        print(*a, file=out, flush=True)

    # the process's counts before this run; the run reads its own ticks
    counts_at_start = dict(TRACE_COUNTS)
    devices = require_chips(cell.chips)
    peaks = spec.peaks(devices[0].device_kind)
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    prog = cell.config["program"]
    mix = cell.traffic

    t = time.perf_counter()
    cfg = serve.model_config(cell.config)
    mesh = cell_mesh(cell, devices)
    params = serve.make_params(cfg, cell.config, seed, mesh)
    jax.block_until_ready(params)
    t_params = time.perf_counter() - t
    t = time.perf_counter()
    engine = serve.make_engine(cfg, params, mesh, mix)
    del params
    engine.warmup()
    loop.warm(engine)
    t_warm = time.perf_counter() - t
    planned = traffic.generate(mix, cell.config["vocab_size"], seed, seconds)
    kernels_before = {k: TRACE_COUNTS[c] - counts_at_start.get(c, 0)
                      for k, c in KERNEL_COUNTERS.items()}

    trace_dir = os.path.join(spec.ROOT, ".bench_trace", cell.name)
    marks, usage = {}, []

    def mark(name):
        if trace and name == "window_open":
            _start_trace(trace_dir)
        marks[name] = (time.perf_counter(), clock.programs)
        usage.append(_usage())

    # set-up's objects go to the permanent generation, so that a full
    # collection in the window walks only what the window made
    gc.collect()
    gc.freeze()
    gcs = GcPauses()
    try:
        log = loop.drive(engine, planned, seconds, mix["window"],
                         drain_limit_s=float(mix.get("drain_limit_s", 0.0)),
                         mark=mark)
    finally:
        gcs.close()
        gc.unfreeze()
    t = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    t_stop = time.perf_counter() - t
    device = _device_record(devices)
    counts = {k: v - counts_at_start.get(k, 0)
              for k, v in TRACE_COUNTS.items()}
    setup_s = log.open - t_start
    compiles_in_window = (marks["window_close"][1] - marks["window_open"][1]
                          if "window_close" in marks else None)
    say(f"setup: {setup_s:.3f} s to the window's opening: parameters "
        f"{t_params:.3f} s, engine build and warm-up {t_warm:.3f} s, "
        f"fill {log.open - log.t0:.3f} s; {clock.programs} programs, "
        f"{clock.seconds:.3f} s in backend compiles, persistent cache "
        f"{cache_dir}: {clock.hits} hits, {clock.writes} entries written; "
        f"compiles inside the window: {compiles_in_window}")
    if len(usage) == 2:
        say(_host_report(log, gcs, usage))
    summary = engine.summary()
    kernels = {k: counts.get(c, 0) for k, c in KERNEL_COUNTERS.items()}
    say(f"engine: decode_executables={summary['decode_executables']} "
        f"quantize_weight_calls={summary['quantize_weight_calls']} "
        f"degrades={summary['health']['degrades']} kernel traces {kernels} "
        f"(before the window {kernels_before})")
    del engine
    gc.collect()

    t = time.perf_counter()
    tr = _read_trace(trace_dir) if trace else None
    if tr is not None:
        say(f"trace: stopped in {t_stop:.3f} s, read and reduced in "
            f"{time.perf_counter() - t:.3f} s; "
            f"{sum(len(v) for v in tr.ops.values())} device events on "
            f"{len(tr.chips)} chips")
        # window_s is what the trace kept, the window busy_s covers;
        # window_marked_s the whole window between the harness's marks
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s,
                      window_marked_s=tr.marked_s)
    run = Run(cell, log, setup_s, tr, peaks, seconds)

    due = run.due_in_window()
    # a mix that drains serves every request due in the window; above
    # the knee the queue grows by design, and what the window attempted
    # is what it admitted
    drains = float(mix.get("drain_limit_s", 0.0)) > 0
    unserved = sum(1 for r in due if not r.token_times) if drains else 0
    attempted = due if drains else [
        r for r in log.records.values() if r.admit and run.in_window(r.admit[0])]
    failed = sum(1 for r in attempted
                 if r.status not in (None, "ok")) + unserved
    ttft = run.first_token_waits_s()
    say(f"window: {len(due)} requests due, {len(run.admits())} admitted, "
        f"{run.tokens_in_window()} tokens, {len(run.decodes())} decode "
        f"steps; ttft p50/p90 {percentile(ttft, 50)}/{percentile(ttft, 90)} "
        f"s; drain {log.end - log.close:.3f} s; queued at the end "
        f"{sum(1 for r in due if r.admit is None)}")

    metrics = {}
    t = time.perf_counter()
    for m in cell.metrics(trace):
        v = spec.load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    say(f"metrics: {len(metrics)} read in {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    cmp = check.compare(cell.config, seed, log.records, mix)
    say(f"check: {cmp['requests']} requests, {cmp['tokens']} served tokens "
        f"against the {cell.config['reference']} reference in "
        f"{time.perf_counter() - t:.3f} s")
    expect = prog.get("kernels", {})
    checks = {
        "logit_gap_max": {"value": cmp["gap_max"],
                          "limit": float(cell.check["logit_gap_limit"])},
        "kernel_fallbacks": {"value": sum(_fallbacks(counts).values()),
                             "limit": 0},
        "expected_kernels_missing": {
            "value": sum(kernels[k] == 0 for k in expect.get("run", [])),
            "limit": 0},
        "bypassed_kernels_traced": {
            "value": sum(kernels[k] > 0 for k in expect.get("bypassed", [])),
            "limit": 0},
        "requests_never_served": {"value": unserved, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": tr.device_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["checks"] = checks
    return result
