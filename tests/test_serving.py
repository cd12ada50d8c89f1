"""Continuous-batching serving engine (PR 6): scheduler correctness,
bitwise parity with the one-shot serve path, slot-reuse hygiene, retrace
and prequant invariants, env hardening, CLI + bench smoke."""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.quant import QuantConfig
from repro.launch.train import scaled_config


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def quant_setup():
    """One small pre-quantized llama3 config + params, shared across the
    engine tests (param init + quantize once; engines are cheap-ish)."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_param_init, param_shardings

    quant = QuantConfig(mode="fp8_e4m3", rotate="hadamard", backend="xla",
                        kv_quant=True)
    cfg = scaled_config(get_config("llama3-8b"), 0.005).with_quant(quant)
    cfg = dataclasses.replace(cfg, weight_quant="int8")
    mesh = make_local_mesh(1)
    with mesh:
        ps = param_shardings(cfg, mesh)
        params = jax.jit(make_param_init(cfg), out_shardings=ps)(
            jax.random.PRNGKey(0))
    return cfg, params, mesh


def _prompts(cfg, n, length, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n, length), dtype=np.int32)


def _one_shot_streams(cfg, params, mesh, prompts, gen, max_len):
    """Reference token streams via the serve.py path (batch prefill +
    scalar-pos lockstep decode), with the cache padded to the SAME
    max_len the engine uses."""
    from repro.launch import shapes as shp
    from repro.launch.steps import jit_prefill_step, jit_serve_step
    from repro.models.lm import pad_kv_caches

    B, P = prompts.shape
    shape = shp.ShapeSpec("serve", "prefill", P, B)
    prefill, _ = jit_prefill_step(cfg, shape, mesh)
    serve, _ = jit_serve_step(cfg, B, max_len, mesh, donate=True)
    batch = {"tokens": jnp.asarray(prompts), "labels": jnp.asarray(prompts)}
    logits, caches = prefill(params, batch)
    caches = pad_kv_caches(cfg, caches, max_len)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out = [np.asarray(tok)]
    for i in range(gen - 1):
        tok, _, caches = serve(params, caches, tok, jnp.asarray(P + i, jnp.int32))
        out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)  # (B, gen)


# ------------------------------------------------- per-slot decode (model)
def test_vector_cache_pos_matches_scalar(quant_setup):
    """lm_decode_step with a (B,) position vector of identical entries is
    bitwise the scalar-pos step: logits AND every cache leaf."""
    from repro.launch import shapes as shp
    from repro.launch.steps import jit_prefill_step
    from repro.models.lm import lm_decode_step, pad_kv_caches

    cfg, params, mesh = quant_setup
    B, P, T = 2, 8, 16
    prompts = _prompts(cfg, B, P)
    prefill, _ = jit_prefill_step(cfg, mesh=mesh,
                                  shape=shp.ShapeSpec("s", "prefill", P, B))
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompts),
                                      "labels": jnp.asarray(prompts)})
    caches = pad_kv_caches(cfg, caches, T)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]

    with mesh:
        l_s, c_s = jax.jit(lambda *a: lm_decode_step(cfg, *a))(
            params, caches, tok, jnp.asarray(P, jnp.int32))
        l_v, c_v = jax.jit(lambda *a: lm_decode_step(cfg, *a))(
            params, caches, tok, jnp.full((B,), P, jnp.int32))
    assert np.array_equal(np.asarray(l_s), np.asarray(l_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------- parity
def test_staggered_parity_bitwise(quant_setup):
    """The tentpole acceptance: staggered-arrival continuous batching
    (fewer slots than requests, so admission waits on a retirement and a
    slot is REUSED) emits per-request greedy token streams bitwise equal
    to the one-shot serve.py path -- on the fp8-KV + hadamard + prequant
    QTensor config."""
    from repro.serving import ServeEngine
    from repro.serving.scheduler import Request

    cfg, params, mesh = quant_setup
    P, GEN, MAXLEN, B = 16, 6, 48, 3
    prompts = _prompts(cfg, B, P)
    base = _one_shot_streams(cfg, params, mesh, prompts, GEN, MAXLEN)

    eng = ServeEngine(cfg, params, mesh, num_slots=2, max_len=MAXLEN,
                      prefill_len=P)
    reqs = [Request(rid=i, tokens=prompts[i], max_new_tokens=GEN,
                    arrival_time=[0.0, 2.0, 4.0][i]) for i in range(B)]
    comps = eng.run(reqs)
    assert len(comps) == B
    for c in comps:
        assert c.finish_reason == "length"
        assert np.array_equal(np.array(c.tokens), base[c.rid]), c.rid
    s = eng.summary()
    # request 2 queued behind fully-occupied slots at least once
    assert s["queue_full_stalls"] >= 1
    # the decode step compiled exactly once across admissions/retirements
    assert s["decode_executables"] == 1
    # prequant QTensor weights: zero per-forward quantize_weight calls
    assert s["quantize_weight_calls"] == 0
    assert s["prefill_inserts"] == B and s["admitted"] == B \
        and s["retired"] == B


def test_slot_reuse_no_stale_kv(quant_setup):
    """A retired-then-reused slot leaks no stale KV: the follow-up
    request's stream is bitwise what it gets in a FRESH engine, even
    though the reused slot's cache rows still hold the predecessor's
    data beyond the new request's range (stale-mask assertion)."""
    from repro.serving import ServeEngine
    from repro.serving.scheduler import Request

    cfg, params, mesh = quant_setup
    P, MAXLEN = 16, 64
    prompts = _prompts(cfg, 2, P, seed=7)
    # r1 generates LONG (fills deep cache rows), r2 short, same slot
    r1 = Request(rid=0, tokens=prompts[0], max_new_tokens=24)
    r2 = Request(rid=1, tokens=prompts[1], max_new_tokens=6,
                 arrival_time=1.0)

    eng_reuse = ServeEngine(cfg, params, mesh, num_slots=1, max_len=MAXLEN,
                            prefill_len=P)
    comps = eng_reuse.run([r1, r2])
    reused = {c.rid: c for c in comps}

    eng_fresh = ServeEngine(cfg, params, mesh, num_slots=1, max_len=MAXLEN,
                            prefill_len=P)
    fresh = {c.rid: c for c in eng_fresh.run([dataclasses.replace(
        r2, arrival_time=0.0)])}

    assert np.array_equal(np.array(reused[1].tokens),
                          np.array(fresh[1].tokens))
    # the reuse run really did leave r1's stale KV in the slot beyond
    # r2's written range: the two engines' cache contents differ ...
    k_reuse = np.asarray(jnp.asarray(eng_reuse.caches[0]["p0"]["k"],
                                     jnp.float32))
    k_fresh = np.asarray(jnp.asarray(eng_fresh.caches[0]["p0"]["k"],
                                     jnp.float32))
    # r2 writes prefill rows [0, P) plus decode rows [P, P+max_new-1)
    depth = P + r2.max_new_tokens - 1
    assert not np.array_equal(k_reuse[:, :, depth:], k_fresh[:, :, depth:])
    # ... while the rows r2 actually wrote agree bitwise
    assert np.array_equal(k_reuse[:, :, :depth], k_fresh[:, :, :depth])


def test_eos_retirement(quant_setup):
    """eos_id retires a request the step the token appears."""
    from repro.serving import ServeEngine
    from repro.serving.scheduler import Request

    cfg, params, mesh = quant_setup
    P, GEN, MAXLEN = 16, 8, 48
    prompts = _prompts(cfg, 1, P, seed=3)
    req = Request(rid=0, tokens=prompts[0], max_new_tokens=GEN)
    eng = ServeEngine(cfg, params, mesh, num_slots=1, max_len=MAXLEN,
                      prefill_len=P)
    full = eng.run([req])[0]
    assert full.finish_reason == "length" and len(full.tokens) == GEN

    eos = full.tokens[2]
    eng2 = ServeEngine(cfg, params, mesh, num_slots=1, max_len=MAXLEN,
                       prefill_len=P, eos_id=int(eos))
    early = eng2.run([req])[0]
    assert early.finish_reason == "eos"
    assert len(early.tokens) <= 3
    assert early.tokens == full.tokens[:len(early.tokens)]


def test_engine_rejects_state_carrying_archs(quant_setup):
    from repro.serving.engine import _validate_config

    rwkv = scaled_config(get_config("rwkv6-7b"), 0.005)
    with pytest.raises(ValueError, match="causal attention"):
        _validate_config(rwkv)


# -------------------------------------------------------------- scheduler
def test_scheduler_freelist_and_stalls():
    from repro.kernels.registry import TRACE_COUNTS
    from repro.serving.scheduler import Request, Scheduler

    sched = Scheduler(num_slots=2, max_len=32, prefill_len=8)
    reqs = [Request(rid=i, tokens=np.zeros(4, np.int32), max_new_tokens=4,
                    arrival_time=float(i)) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    assert sched.counters["submitted"] == 3

    # nothing has arrived at t<0 -- not a stall, just no work yet
    assert sched.next_admission(-1.0) is None
    assert sched.counters["queue_full_stalls"] == 0

    s0, r0 = sched.next_admission(0.0)
    assert (s0, r0.rid) == (0, 0)
    s1, r1 = sched.next_admission(1.0)
    assert (s1, r1.rid) == (1, 1)
    # arrived head + all slots busy = a counted stall
    stalls0 = TRACE_COUNTS[("serving", "queue_full_stall")]
    assert sched.next_admission(2.0) is None
    assert sched.counters["queue_full_stalls"] == 1
    assert TRACE_COUNTS[("serving", "queue_full_stall")] == stalls0 + 1

    # LIFO free list: the just-retired slot is reused immediately
    sched.retire(s1, "length", 3.0)
    s2, r2 = sched.next_admission(3.0)
    assert (s2, r2.rid) == (1, 2)
    assert sched.occupancy == 1.0
    sched.retire(s0, "length", 4.0)
    sched.retire(s2, "eos", 4.0)
    assert not sched.has_work()
    assert sorted(sched.free) == [0, 1]
    assert sched.counters["admitted"] == 3 and sched.counters["retired"] == 3


def test_scheduler_validates_requests():
    from repro.serving.scheduler import Request, Scheduler

    sched = Scheduler(num_slots=1, max_len=16, prefill_len=8)
    with pytest.raises(ValueError, match="prompt_len"):
        sched.submit(Request(0, np.zeros(9, np.int32), 2))
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request(0, np.zeros(8, np.int32), 9))
    with pytest.raises(ValueError, match="prefill_len"):
        Scheduler(num_slots=1, max_len=8, prefill_len=16)


# ------------------------------------------------------------ env hardening
def test_harden_host_env_sets_flags(tmp_path, monkeypatch):
    from repro.launch import env as env_mod

    lib = tmp_path / "libtcmalloc.so.4"
    lib.write_bytes(b"")
    monkeypatch.setattr(env_mod, "_TCMALLOC_CANDIDATES", (str(lib),))
    env = {}
    applied = env_mod.harden_host_env(environ=env)
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert env["LD_PRELOAD"] == str(lib)
    assert env[env_mod._MARKER] == "1"
    assert set(applied) == {"TF_CPP_MIN_LOG_LEVEL",
                            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                            "LD_PRELOAD"}
    # idempotent: marker blocks a second preload mutation
    assert "LD_PRELOAD" not in env_mod.harden_host_env(environ=env)


def test_harden_host_env_opt_out_and_preservation(monkeypatch):
    from repro.launch import env as env_mod

    assert env_mod.harden_host_env(
        environ={"REPRO_NO_ENV_HARDEN": "1"}) == {}

    monkeypatch.setattr(env_mod, "_TCMALLOC_CANDIDATES", ())
    env = {"TF_CPP_MIN_LOG_LEVEL": "0",
           "REPRO_XLA_HOST_DEVICES": "4", "XLA_FLAGS": "--foo"}
    applied = env_mod.harden_host_env(environ=env)
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "0"          # user's value wins
    assert env["XLA_FLAGS"] == \
        "--foo --xla_force_host_platform_device_count=4"
    assert "LD_PRELOAD" not in env                     # no tcmalloc found
    assert "XLA_FLAGS" in applied


def test_enable_compile_cache_placement():
    from repro.launch import env as env_mod

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # set from outside: JAX reads the variables; nothing is set in code
        assert env_mod.enable_compile_cache(
            {"JAX_COMPILATION_CACHE_DIR": "/elsewhere",
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "5"}
        ) == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == min_s
        # otherwise: the fixed directory inside the checkout, every program
        want = os.path.join(repo_root, ".jax_cache")
        assert env_mod.enable_compile_cache({}) == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert not jax.config.jax_enable_compilation_cache   # tests stay off
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


# ------------------------------------------------------------- CLI + bench
def test_serve_loop_cli_runs(capsys):
    from repro.launch.serve_loop import main

    main(["--arch", "llama3-8b", "--scale", "0.004", "--slots", "2",
          "--max-len", "32", "--prefill-len", "8", "--requests", "3",
          "--rate", "1.0", "--prompt-min", "4", "--gen-min", "3",
          "--gen-max", "5", "--quant", "int8", "--rotate", "hadamard"])
    out = capsys.readouterr().out
    assert "pre-quantized once at load" in out
    assert "warmup:" in out
    assert "tok/s" in out and "p50" in out and "p99" in out
    assert "decode_executables=1" in out
    assert "quantize_weight_calls=0" in out


def test_bench_serve_loop_smoke():
    from benchmarks import bench_serve_loop

    csv, records = [], []
    bench_serve_loop.run(csv, smoke=True, records=records)
    assert any("serve_loop" in line for line in csv)
    assert all({"bench", "shape", "dtype", "backend", "ms", "gbps"}
               <= set(r) for r in records)
    assert all(r["ms"] > 0 for r in records)
    modes = {r["bench"] for r in records}
    assert modes == {"serve_loop_none", "serve_loop_int8",
                     "serve_loop_overload"}
    # the overload flood must actually overload: every disposition class
    # is recorded, and load was genuinely shed/rejected
    ov = next(r for r in records if r["bench"] == "serve_loop_overload")
    assert {"ok", "timed_out", "rejected", "degraded", "shed",
            "p99_ms"} <= set(ov)
    assert ov["rejected"] > 0 and ov["timed_out"] > 0
    assert ov["ok"] + ov["timed_out"] + ov["rejected"] + ov["degraded"] == 10


# --------------------------------------------------------- spans + scopes
def _engine_spans(xplane: str):
    """The engine's host spans of one trace file, [name, start, end],
    parents before their children."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    spans = [[e.name.partition("#")[0], e.start_ns, e.start_ns + e.duration_ns]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    name, a, b = parent
    return [s[0] for s in spans
            if s[0].rpartition(".")[0] == name and a <= s[1] and s[2] <= b]


def test_engine_spans_partition_each_step(quant_setup, tmp_path):
    """Under a profiler, every admission holds its prefill, insert and
    readback spans and every decode step one dispatch, readback and
    bookkeep, in that order; the tokens are bitwise those of the same
    engine run with no profiler, and ``summary()`` times decode steps and
    rates tokens over the wall time served."""
    import glob

    from repro.serving import ServeEngine
    from repro.serving.scheduler import Request

    cfg, params, mesh = quant_setup
    P, MAXLEN = 8, 32
    prompts = _prompts(cfg, 2, P, seed=5)
    reqs = [Request(rid=i, tokens=prompts[i], max_new_tokens=4 + i,
                    arrival_time=float(i)) for i in range(2)]

    def engine():
        return ServeEngine(cfg, params, mesh, num_slots=2, max_len=MAXLEN,
                           prefill_len=P)

    traced = engine()
    traced.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        got = {c.rid: c.tokens for c in traced.run(reqs)}
    plain = engine()
    want = {c.rid: c.tokens for c in plain.run(reqs)}
    assert got == want

    [xplane] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _engine_spans(xplane)
    admits = [s for s in spans if s[0] == "engine.admit"]
    decodes = [s for s in spans if s[0] == "engine.decode"]
    s = traced.summary()
    assert len(admits) == 2 and len(decodes) == s["decode_steps"] > 0
    for a in admits:
        assert _children(spans, a) == ["engine.admit.prefill",
                                       "engine.admit.insert",
                                       "engine.admit.readback"]
    for d in decodes:
        assert _children(spans, d) == ["engine.decode.dispatch",
                                       "engine.decode.readback",
                                       "engine.decode.bookkeep"]

    steps = traced._step_latencies_ms
    assert s["p50_token_ms"] == pytest.approx(float(np.percentile(steps, 50)))
    assert s["decode_s"] == pytest.approx(sum(steps) * 1e-3)
    # the wall time served holds the admissions too
    assert 0 < s["tokens_per_s"] < s["generated_tokens"] / s["decode_s"]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_programs_carry_model_scopes(quant_setup, program):
    """The compiled steps name the model's scopes in their op_name
    metadata: the layer scan's body holds attention and mlp; embedding and
    logits sit outside it."""
    import re

    from repro.serving import ServeEngine

    cfg, params, mesh = quant_setup
    eng = ServeEngine(cfg, params, mesh, num_slots=2, max_len=32,
                      prefill_len=8)
    if program == "decode":
        lowered = eng._decode.lower(eng.params, eng.caches,
                                    jnp.asarray(eng.tokens_h),
                                    jnp.asarray(eng.positions_h))
    else:
        lowered = eng._prefill.lower(
            eng.params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
            jnp.asarray(1, jnp.int32))
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    for scope in ("attention", "mlp"):
        assert any(re.match(rf"jit\(\w+\)/layers/while/body/.*{scope}/", n)
                   for n in names), scope
    for scope in ("embed", "logits"):
        assert any(re.match(rf"jit\(\w+\)/{scope}/", n) for n in names), scope
