"""Continuous-batching serving engine over pre-quantized QTensor weights.

The one-shot launcher (``launch/serve.py``) prefills a fixed batch, then
decodes every row in lockstep behind a single scalar ``pos`` until the
whole batch exits together. A production serving loop admits and retires
requests *mid-decode*. This engine does that with three jitted device
functions, each compiled exactly once per engine:

  prefill   (params, {tokens:(1,P)}, length) -> (first token, KV rows)
            -- prompts are right-padded to the fixed prefill bucket P, so
            every admission hits the same compiled executable; under the
            causal mask the padding rows never influence positions
            < length, and the logits are gathered at length-1.
  insert    (caches, kv, slot) -> caches    [donated caches]
            -- scatter the newcomer's KV block into its slot.
  decode    (params, caches, tokens, positions) -> tokens [donated caches]
            -- ``launch.steps.jit_serve_step(per_slot=True)``: one step
            over ALL slots with a (slots,) position vector; every slot
            writes and attends at its own depth.

The KV cache is allocated ONCE (``serving.cache``) in the serving quant
dtype; admissions, retirements, and slot reuse are host-side scheduler
bookkeeping (``serving.scheduler``) plus donated in-place updates -- the
steady-state decode step neither reallocates nor retraces (the decode
executable count stays 1 across the whole run unless the degradation
ladder re-warms; see ``decode_cache_size``). With ``cfg.weight_quant ==
'int8'`` the weights are pre-quantized QTensors, so the serving forward
performs zero ``quantize_weight`` calls after engine construction
(tracked via ``wquant.QUANTIZE_WEIGHT_CALLS``).

Robustness layer (PR 8, DESIGN.md section 12):

  * request lifecycle -- per-request deadlines (expired queued requests
    shed before admission; in-flight slots past deadline retired as
    ``timed_out``), bounded admission queue with immediate ``rejected``
    completions (``max_queue``);
  * decode watchdog -- ``watchdog_ms`` bounds per-step wall clock; the
    check is post-hoc (a synchronous jit dispatch cannot be preempted),
    so a slow step's result is still used, and two CONSECUTIVE trips
    trigger a degradation re-warm;
  * graceful degradation ladder -- a decode dispatch that raises is
    retried once on intact caches (faults fire at the host boundary,
    BEFORE the donated operands are consumed), then the engine re-warms
    one rung down: pallas/streamed -> pallas/rotate_once -> xla. Every
    rung is bitwise-identical by construction (asserted by the
    quant_dot parity tests), so mid-run degradation never changes
    emitted tokens. Rung switches tick
    ``TRACE_COUNTS[("serving", "degrade_<rung>")]`` and warn once;
  * numeric guardrails -- with ``REPRO_NUMERIC_GUARDS=1`` the jitted
    steps carry isfinite/positive-scale reductions
    (``core.guards``); a tripped slot is retired as ``degraded``
    (reason ``nan_guard``) at the step boundary instead of emitting
    poisoned tokens. Guard-off and guard-on runs are bitwise identical
    on healthy requests (guards observe, never perturb).

ABFT layer (PR 10, DESIGN.md section 14): with ``REPRO_ABFT=1`` (or
``QuantConfig.abft``) the engine serves checksum-VERIFIED steps --
silent-data-corruption detection for finite-but-wrong values the
isfinite guards cannot see. Weight checksums are attached at init
(``verify.with_checks``); the fused quant_dot kernels verify their own
outputs in-kernel and NaN-poison failing rows into the logits seam; the
decode step carries a per-slot KV conservation state (fifth jit
argument, donated) that recomputes and cross-checks the cache sums
every step. A tripped slot retires as ``sdc_detected``
(``Completion.status`` 'degraded') -- KV trips attribute directly,
logits trips attribute by re-verifying the stored weight checksums
against the live weights (corrupt -> ``sdc_detected``, clean ->
``nan_guard``). Two detections within ``_SDC_WINDOW_STEPS`` re-warm
the degradation ladder one rung. Healthy ABFT-on runs are bitwise
identical to ABFT-off (exact selects only; asserted in
tests/test_faults.py).

Fault injection (tests): ``repro.testing.faults`` installs a context-
scoped ``FaultPlan`` the engine polls at each decode dispatch --
synthetic kernel raises, artificial step latency, NaN pokes into live
KV rows. Zero-fault overhead is one attribute load + None check.

Timing discipline: ``warmup()`` pays all three compiles on dummy inputs
before any request is admitted, so reported decode-step times are
steady-state (the same fix applied to ``serve.py``'s timed loop).

Spans: each admission and decode step is a ``jax.profiler``
annotation, recorded only while a profiler trace runs, on the clock of
the device operations. A name is the text before any ``#``; the
sub-spans of a step partition it up to a few microseconds of glue.

  engine.admit            all of ``_admit`` (stats: rid, slot, prompt_len)
    .prefill              pad the prompt, copy it over, dispatch prefill
    .insert               dispatch the KV insert into the slot
    .readback             block on the first token (and, guards on, on
                          the prefill's ok flag, before the insert)
  engine.decode           all of ``_decode_step`` (a step annotation,
                          ``step_num`` = ``self.step``)
    .dispatch             fault hooks, the host->device copies of tokens
                          and positions, the decode call until it
                          returns (retries and re-warms included)
      .kv_check           ABFT only: the pre-step check, blocked on
    .readback             block on the step and copy its tokens (and
                          guard flags) to the host
      .kv_roll            ABFT only: the post-step roll, blocked on
    .bookkeep             the watchdog, then each slot's append/retire
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import verify
from repro.core import guards, wquant
from repro.distributed import sharding as shd
from repro.kernels.registry import TRACE_COUNTS, warn_once
from repro.launch.steps import jit_serve_step
from repro.models.config import ModelConfig
from repro.models.lm import lm_forward
from repro.serving.cache import alloc_kv_caches, cache_bytes, make_insert_fn
from repro.serving.scheduler import Completion, Request, Scheduler
from repro.testing import faults

_SUPPORTED_KINDS = ("attn", "moe")

# TRACE_COUNTS keys snapshotted at engine construction so ``health()``
# can report per-engine deltas of the process-global counters.
_HEALTH_TRACE_KEYS = (
    ("abft", "kv_trip"),
    ("abft", "sdc_detected"),
    ("abft", "params_check"),
    ("serving", "guard_trip"),
    ("serving", "watchdog_trip"),
    ("serving", "step_retry"),
    ("serving", "deadline_retire"),
)

# ABFT degradation window: >= 2 SDC detections within this many engine
# steps re-warm the ladder one rung (sustained corruption, not a blip).
_SDC_WINDOW_STEPS = 16


def _validate_config(cfg: ModelConfig) -> None:
    """Continuous batching needs position-addressable per-token caches;
    right-padded bucket prefill is only exact for causal attention (a
    padded row can never influence an earlier position). Scan-state
    architectures (mamba/rwkv) carry their whole prefix in one state
    tensor, so a padded prefill would fold padding into the state."""
    kinds = {k for pattern, _ in cfg.groups for k in pattern}
    bad = kinds - set(_SUPPORTED_KINDS)
    if bad or cfg.is_encdec or cfg.family == "vlm":
        raise ValueError(
            f"serving engine supports causal attention stacks only "
            f"(kinds {_SUPPORTED_KINDS}); config {cfg.name!r} has "
            f"kinds={sorted(kinds)} family={cfg.family!r} "
            f"encdec={cfg.is_encdec}")


def _make_prefill_fn(cfg: ModelConfig, guard: bool = False):
    def prefill(params, batch, length):
        logits, _, caches = lm_forward(cfg, params, batch, want_cache=True)
        # right-padded bucket: the request's last real token sits at
        # length-1; everything past it is padding the causal mask keeps
        # out of positions < length
        last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)
        tok = jnp.argmax(last[:, -1], axis=-1).astype(jnp.int32)
        return tok, caches

    if not guard:
        return prefill

    def guarded_prefill(params, batch, length):
        logits, _, caches = lm_forward(cfg, params, batch, want_cache=True)
        last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)
        ok = guards.rows_ok(last[:, -1], batch["tokens"].shape[0])
        tok = jnp.argmax(last[:, -1], axis=-1).astype(jnp.int32)
        return tok, ok, caches

    return guarded_prefill


def _degradation_ladder(cfg: ModelConfig) -> List[ModelConfig]:
    """The rungs below ``cfg``, most-capable first. Every rung computes
    bitwise-identical results (schedule/backend parity is asserted by the
    quant_dot tests); each is strictly simpler machinery:

        pallas + streamed  ->  pallas + rotate_once  ->  xla

    A config already on 'xla' has no lower rung: a failure there
    exhausts the ladder and fails the in-flight requests loudly."""
    ladder = [cfg]
    q = cfg.quant
    if q.backend in ("pallas", "auto"):
        if q.schedule != "rotate_once":
            ladder.append(cfg.with_quant(
                dataclasses.replace(q, schedule="rotate_once")))
        ladder.append(cfg.with_quant(
            dataclasses.replace(q, backend="xla", schedule=None)))
    elif q.backend == "ref":
        ladder.append(cfg.with_quant(
            dataclasses.replace(q, backend="xla", schedule=None)))
    return ladder


def _rung_name(cfg: ModelConfig) -> str:
    q = cfg.quant
    if q.backend == "xla":
        return "xla"
    return q.schedule or "default"


class ServeEngine:
    """Drives jitted prefill/insert/decode steps over a request stream.

    params must already be placed with ``launch.steps.param_shardings``
    (the launchers' init path); with ``cfg.weight_quant == 'int8'`` they
    are the pre-quantized QTensor tree."""

    def __init__(self, cfg: ModelConfig, params, mesh, *,
                 num_slots: int, max_len: int, prefill_len: int,
                 eos_id: Optional[int] = None, rules_overrides=None,
                 max_queue: Optional[int] = None,
                 watchdog_ms: Optional[float] = None):
        _validate_config(cfg)
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.eos_id = eos_id
        self.prefill_len = prefill_len
        self.max_len = max_len
        self.sched = Scheduler(num_slots, max_len, prefill_len,
                               max_queue=max_queue)
        self._rules_overrides = rules_overrides
        self._guard = guards.guards_enabled()
        self._abft = (bool(getattr(cfg.quant, "abft", False))
                      or verify.abft_enabled())
        if self._abft:
            # weights quantized without checksums (abft switched on after
            # load) get them attached here, once; check-carrying leaves
            # pass through verbatim
            self.params = verify.with_checks(self.params)
            self._kv_reset = jax.jit(verify.kv_slot_reset,
                                     donate_argnums=(0,))
            # the KV conservation check is deliberately NOT folded into
            # the decode executable: that program donates its cache
            # operands, and a whole-cache read inside it forces XLA to
            # defensively copy the donated buffers (see verify.kv_check)
            self._kv_check = jax.jit(verify.kv_check)
            self._kv_roll = jax.jit(verify.kv_roll)
        self._sdc_trips: collections.deque = collections.deque(maxlen=8)
        self._params_check_step = -1
        self._params_check_ok = True
        self._trace_base = {k: TRACE_COUNTS[k] for k in _HEALTH_TRACE_KEYS}
        self._watchdog_ms = watchdog_ms
        self._watchdog_skip = 0       # steps exempted after a re-warm
        self._consec_slow = 0

        self._ladder = _degradation_ladder(cfg)
        self._rung = 0
        self._decode_jits: list = []

        # insert is rung-independent (a pure cache scatter: its trace
        # never touches quant schedule or backend), so it is compiled
        # once and shared across every rung
        self._insert = jax.jit(self._in_rules(make_insert_fn(cfg)),
                               donate_argnums=(0,))
        self._bind_rung(0)

        # the ONE cache allocation of the engine's lifetime
        cs = self._decode_shardings[1]
        self.caches = jax.device_put(
            alloc_kv_caches(cfg, num_slots, max_len), cs)
        # ABFT KV conservation state: per-slot [sum, abs_sum] over the
        # slot's valid rows, carried across steps and checked/rolled by
        # the kv_check/kv_roll executables dispatched around each decode
        # (repro.verify, DESIGN.md section 14)
        self.kv_sums = (jnp.zeros((num_slots, 2), jnp.float32)
                        if self._abft else None)
        self.tokens_h = np.zeros((num_slots, 1), np.int32)
        self.positions_h = np.zeros((num_slots,), np.int32)

        self.step = 0
        self.completions: List[Completion] = []
        self._step_latencies_ms: List[float] = []
        self._occupancy: List[float] = []
        self._serve_s = 0.0
        self._compile_s: Optional[float] = None
        self._idle_steps = 0
        self._qw_calls_baseline = wquant.QUANTIZE_WEIGHT_CALLS

    def _in_rules(self, fn):
        mesh, overrides = self.mesh, self._rules_overrides

        def wrapped(*a):
            with shd.sharding_rules(mesh, overrides):
                return fn(*a)
        return wrapped

    def _bind_rung(self, i: int) -> None:
        """Compile-bind the jitted prefill/decode for ladder rung ``i``
        (lazily compiled on first call, as all jax.jit wrappers are)."""
        cfg = self._ladder[i]
        self._rung = i
        # ABFT implies the guarded prefill/decode seam: the kernel
        # checksum residual surfaces as NaN-poisoned logit rows there,
        # and the decode executable itself stays the plain guarded step
        # (the KV check rides in separate kv_check/kv_roll programs)
        self._prefill = jax.jit(self._in_rules(
            _make_prefill_fn(cfg, guard=self._guard or self._abft)))
        self._decode, self._decode_shardings = jit_serve_step(
            cfg, self.sched.num_slots, self.max_len, self.mesh,
            rules_overrides=self._rules_overrides,
            donate=True, per_slot=True,
            guard=self._guard or self._abft)
        self._decode_jits.append(self._decode)

    # ---------------------------------------------------------- warm-up
    def warmup(self) -> float:
        """Compile prefill/insert/decode on dummy inputs before serving,
        so no request's latency includes a jit compile. Writes garbage
        into cache rows that are by-construction never attended before
        being overwritten (prefill-insert rewrites [0, P) on admission;
        decode rewrites row ``pos`` before attending it)."""
        if self._compile_s is not None:
            return self._compile_s
        t0 = time.perf_counter()
        batch = {"tokens": jnp.zeros((1, self.prefill_len), jnp.int32)}
        out = self._prefill(self.params, batch, jnp.asarray(1, jnp.int32))
        kv = out[-1]
        self.caches = self._insert(self.caches, kv,
                                   jnp.asarray(0, jnp.int32))
        new_tok, _, self.caches = self._decode(
            self.params, self.caches, jnp.asarray(self.tokens_h),
            jnp.asarray(self.positions_h))
        if self._abft:
            # compile the conservation-check executables too (positions
            # are all zero -> zero valid rows, so the warmup's garbage
            # KV writes are invisible to the sums and ok is all-True)
            pos = jnp.zeros((self.sched.num_slots,), jnp.int32)
            _, cur = self._kv_check(self.caches, pos, self.kv_sums)
            jax.block_until_ready(self._kv_roll(self.caches, pos, cur))
        jax.block_until_ready(new_tok)
        self._compile_s = time.perf_counter() - t0
        # everything past this point is steady-state serving
        self._qw_calls_baseline = wquant.QUANTIZE_WEIGHT_CALLS
        return self._compile_s

    # ------------------------------------------------------- degradation
    def _degrade(self, why: str) -> bool:
        """Re-warm one rung down the ladder; False when exhausted. The
        new rung's prefill is compiled eagerly here (its dummy run
        touches no engine state); the decode executable compiles on its
        first real dispatch -- that step is exempted from the watchdog
        so a compile is not mistaken for a hang."""
        if self._rung + 1 >= len(self._ladder):
            warn_once(
                ("serving", "ladder_exhausted"),
                f"serving degradation ladder exhausted ({why}); failing "
                "in-flight requests (warned once per process; "
                "TRACE_COUNTS[('serving', 'ladder_exhausted')] keeps "
                "counting)")
            return False
        self._bind_rung(self._rung + 1)
        name = _rung_name(self._ladder[self._rung])
        self.sched.counters["degrades"] += 1
        warn_once(
            ("serving", f"degrade_{name}"),
            f"serving engine degraded to rung '{name}' "
            f"({self._rung + 1}/{len(self._ladder)}) after {why}; outputs "
            "are bitwise-unchanged (schedule/backend parity) -- warned "
            f"once per process; TRACE_COUNTS[('serving', 'degrade_{name}')]"
            " keeps counting")
        # eager prefill compile: the result is discarded, no engine
        # state is touched (prefill donates nothing)
        batch = {"tokens": jnp.zeros((1, self.prefill_len), jnp.int32)}
        out = self._prefill(self.params, batch, jnp.asarray(1, jnp.int32))
        jax.block_until_ready(out[0])
        self._watchdog_skip = 1
        self._consec_slow = 0
        return True

    def _fail_inflight(self, why: str) -> None:
        """Ladder exhausted: retire every active slot as degraded and
        drain the queue -- the engine never crashes the caller."""
        now = float(self.step)
        TRACE_COUNTS[("serving", "ladder_exhausted")] += 1
        for slot in sorted(self.sched.active):
            self.completions.append(
                self.sched.retire(slot, "engine_failed", now))
        queued = list(self.sched.queue)
        self.sched.queue.clear()
        self.sched.counters["shed"] += len(queued)
        for req in queued:
            self.completions.append(
                self.sched._unadmitted_completion(req, "shed_engine_failed"))

    # --------------------------------------------------------- lifecycle
    def submit(self, req: Request) -> Optional[Completion]:
        """Returns None on acceptance, or the ``rejected`` completion
        when the bounded queue pushed back (also appended to
        ``self.completions``)."""
        rejected = self.sched.submit(req)
        if rejected is not None:
            self.completions.append(rejected)
        return rejected

    def _admit(self, slot: int, req: Request) -> None:
        with TraceAnnotation("engine.admit", rid=req.rid, slot=slot,
                             prompt_len=req.prompt_len):
            with TraceAnnotation("engine.admit.prefill"):
                padded = np.zeros((1, self.prefill_len), np.int32)
                padded[0, :req.prompt_len] = req.tokens
                out = self._prefill(
                    self.params, {"tokens": jnp.asarray(padded)},
                    jnp.asarray(req.prompt_len, jnp.int32))
            if self._guard or self._abft:
                tok, ok, kv = out
                with TraceAnnotation("engine.admit.readback"):
                    ok_h = bool(np.asarray(ok)[0])
                if not ok_h:
                    # poisoned prefill: never insert, never emit -- retire
                    # the freshly admitted slot as degraded on the spot.
                    # With ABFT on, attribute first: a stale weight
                    # checksum means silent corruption (sdc_detected), a
                    # clean one a transient numeric event (nan_guard).
                    reason = "nan_guard"
                    if self._abft and self._weights_corrupt():
                        reason = "sdc_detected"
                        self._note_sdc()
                    else:
                        self.sched.counters["guard_trips"] += 1
                        TRACE_COUNTS[("serving", "guard_trip")] += 1
                    self.completions.append(self.sched.retire(
                        slot, reason, float(self.step)))
                    return
            else:
                tok, kv = out
            with TraceAnnotation("engine.admit.insert"):
                self.caches = self._insert(self.caches, kv,
                                           jnp.asarray(slot, jnp.int32))
            with TraceAnnotation("engine.admit.readback"):
                tok_h = int(jax.block_until_ready(tok)[0])
            self.sched.counters["prefill_inserts"] += 1

            st = self.sched.active[slot]
            st.generated.append(tok_h)
            self.tokens_h[slot, 0] = tok_h
            self.positions_h[slot] = st.pos
            if self._abft:
                # rebase the slot's conservation state from the freshly
                # inserted KV block (insert rewrites the block wholesale);
                # blocked so this cache read cannot still be in flight
                # when the next decode donates the buffers it walks
                self.kv_sums = jax.block_until_ready(self._kv_reset(
                    self.kv_sums, self.caches, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(int(st.pos), jnp.int32)))
            self._maybe_retire(slot, tok_h)

    def _maybe_retire(self, slot: int, last_tok: int) -> bool:
        st = self.sched.active[slot]
        reason = None
        if self.eos_id is not None and last_tok == self.eos_id:
            reason = "eos"
        elif len(st.generated) >= st.max_new_tokens:
            reason = "length"
        elif st.pos >= self.max_len:
            reason = "cache_full"
        if reason is None:
            return False
        self.completions.append(
            self.sched.retire(slot, reason, float(self.step)))
        return True

    def _retire_expired_inflight(self, now: float) -> None:
        for slot in sorted(self.sched.active):
            st = self.sched.active[slot]
            if st.deadline is not None and st.deadline <= now:
                self.sched.counters["deadline_retired"] += 1
                TRACE_COUNTS[("serving", "deadline_retire")] += 1
                self.completions.append(
                    self.sched.retire(slot, "deadline", now))

    # -------------------------------------------------------------- run
    def run(self, requests: Sequence[Request]) -> List[Completion]:
        """Serve a whole arrival stream to completion; returns the
        completion records (also accumulated on ``self.completions``)."""
        self.warmup()
        t0 = time.perf_counter()
        for req in requests:
            self.submit(req)
        while self.sched.has_work():
            now = float(self.step)
            # shed queued requests whose TTL expired before a slot freed
            self.completions.extend(self.sched.shed_expired(now))
            # retire in-flight slots past their deadline (distinct
            # status from a natural finish)
            self._retire_expired_inflight(now)
            # admissions: prefill-insert every arrived request a free
            # slot can take, straight into the running decode batch
            while True:
                adm = self.sched.next_admission(now)
                if adm is None:
                    break
                self._admit(*adm)
            if not self.sched.active:
                nxt = self.sched.next_arrival()
                if nxt is None:
                    break
                # idle: jump the step clock to the next arrival
                self.step = max(self.step + 1, int(np.ceil(nxt)))
                self._idle_steps += 1
                continue
            self._decode_step()
        self._serve_s += time.perf_counter() - t0
        return self.completions

    def _inject_faults(self) -> None:
        """Apply this step's scheduled state corruptions (NaN pokes,
        silent bit flips / row perturbations / tile clobbers) at the TOP
        of the step, before the ABFT kv_check reads the caches -- so a
        corruption landing at step N is detectable at step N, exactly
        like a cosmic-ray flip that happened between dispatches."""
        plan = faults.active()
        if plan is None:
            return
        if plan.should_poke(self.step):
            row = int(self.positions_h[plan.nan_poke_slot]) - 1
            if row >= 0:
                self.caches = faults.poke_nan(
                    self.caches, plan.nan_poke_slot, row)
        if plan.should_corrupt(self.step):
            self._inject_corruption(plan)

    def _dispatch_decode(self):
        """One decode dispatch at the current rung, with the per-attempt
        fault hooks at the host boundary: an injected raise fires BEFORE
        the jitted call, so the donated caches were not consumed and a
        retry runs on intact state."""
        plan = faults.active()
        if plan is not None:
            d = plan.delay_s(self.step)
            if d > 0.0:
                time.sleep(d)
            plan.maybe_raise(self.step)
        return self._decode(
            self.params, self.caches, jnp.asarray(self.tokens_h),
            jnp.asarray(self.positions_h))

    def _inject_corruption(self, plan) -> None:
        """Apply a scheduled SILENT corruption at the host boundary
        (params are never donated; the cache write goes through the same
        functional update path as ``poke_nan``)."""
        if plan.corrupt_kind == "weight":
            self.params = faults.flip_weight_bit(self.params,
                                                 bit=plan.corrupt_bit)
        elif plan.corrupt_kind == "kv":
            row = int(self.positions_h[plan.kv_corrupt_slot]) - 1
            if row >= 0:
                self.caches = faults.perturb_kv_row(
                    self.caches, plan.kv_corrupt_slot, row)
        elif plan.corrupt_kind == "tile":
            self.params = faults.clobber_stream_tile(self.params)
        else:
            raise ValueError(
                f"unknown corrupt_kind {plan.corrupt_kind!r}")

    def _decode_with_recovery(self):
        """Dispatch; on failure retry ONCE on the same rung (transient
        fault, caches intact), then walk the degradation ladder. None =
        ladder exhausted."""
        try:
            return self._dispatch_decode()
        except Exception as e:
            first = e
        self.sched.counters["step_retries"] += 1
        TRACE_COUNTS[("serving", "step_retry")] += 1
        try:
            return self._dispatch_decode()
        except Exception:
            pass
        while self._degrade(f"decode failure: {first!r}"):
            try:
                return self._dispatch_decode()
            except Exception:
                continue
        return None

    # -------------------------------------------------------------- abft
    def _weights_corrupt(self) -> bool:
        """On-demand weight attribution after a logits-level trip: do the
        live weights still match their stored ABFT checksums? Cached per
        engine step so one corrupted step verifies the tree once however
        many slots tripped."""
        if self._params_check_step != self.step:
            self._params_check_step = self.step
            TRACE_COUNTS[("abft", "params_check")] += 1
            self._params_check_ok = verify.params_ok(self.params)
        return not self._params_check_ok

    def _note_sdc(self) -> None:
        """Record an SDC detection; sustained detections (>= 2 within
        ``_SDC_WINDOW_STEPS`` engine steps) feed the degradation ladder:
        if the corruption lives in one rung's machinery (a sick kernel
        path, a bad stream buffer) the re-warm clears it, and if not the
        ladder eventually exhausts and fails loudly -- never silently."""
        TRACE_COUNTS[("abft", "sdc_detected")] += 1
        self.sched.counters["sdc_retired"] += 1
        self._sdc_trips.append(self.step)
        recent = [s for s in self._sdc_trips
                  if self.step - s <= _SDC_WINDOW_STEPS]
        if len(recent) >= 2:
            self._sdc_trips.clear()
            self._degrade("repeated ABFT SDC detections")

    def _abft_rebase_slot(self, slot: int) -> None:
        """Re-anchor one slot's KV conservation state to the cache as it
        is NOW, over the slot's current row count. Called when a slot is
        retired mid-trip (its position stops advancing, so the carried
        sum+delta rollforward would drift from the recompute) -- after
        this, a dead slot verifies trivially until reuse rebases it
        again at insert."""
        self.kv_sums = jax.block_until_ready(self._kv_reset(
            self.kv_sums, self.caches, jnp.asarray(slot, jnp.int32),
            jnp.asarray(int(self.positions_h[slot]), jnp.int32)))

    def _decode_step(self) -> None:
        with StepTraceAnnotation("engine.decode", step_num=self.step):
            t0 = time.perf_counter()
            kv_ok = cur = pos = None
            with TraceAnnotation("engine.decode.dispatch"):
                self._inject_faults()
                if self._abft:
                    # pre-decode integrity gate on the exact caches the
                    # donated step is about to consume. block_until_ready
                    # serializes the read against the donated in-place
                    # reuse: an async-pending whole-cache read racing a
                    # donation is a runtime conflict, not a dataflow edge
                    with TraceAnnotation("engine.decode.kv_check"):
                        pos = jnp.asarray(self.positions_h)
                        kv_ok, cur = self._kv_check(self.caches, pos,
                                                    self.kv_sums)
                        jax.block_until_ready(cur)
                out = self._decode_with_recovery()
            if out is None:
                self._fail_inflight("decode failed on every ladder rung")
                return
            new_tok, mid, self.caches = out
            with TraceAnnotation("engine.decode.readback"):
                ok_h = (np.asarray(mid) if (self._guard or self._abft)
                        else None)
                kv_ok_h = None
                if self._abft:
                    # roll the conservation state over the one row the
                    # step just wrote per slot (at the pre-step
                    # positions); blocked for the same reason as the
                    # pre-step check -- the NEXT step donates the cache
                    # buffers this read walks
                    with TraceAnnotation("engine.decode.kv_roll"):
                        self.kv_sums = jax.block_until_ready(
                            self._kv_roll(self.caches, pos, cur))
                    kv_ok_h = np.asarray(kv_ok)
                new_tok_h = np.asarray(new_tok)       # blocks until ready
            with TraceAnnotation("engine.decode.bookkeep"):
                self._bookkeep((time.perf_counter() - t0) * 1e3,
                               new_tok_h, ok_h, kv_ok_h)

    def _bookkeep(self, dt_ms: float, new_tok_h, ok_h, kv_ok_h) -> None:
        """After a decode step: the watchdog, then each slot's token
        appended, or the slot retired where its guard or KV check
        tripped or it finished."""
        self._step_latencies_ms.append(dt_ms)
        self._occupancy.append(self.sched.occupancy)
        self.step += 1
        self._watchdog(dt_ms)
        for slot in sorted(self.sched.active):
            st = self.sched.active[slot]
            if kv_ok_h is not None and not bool(kv_ok_h[slot]):
                # KV conservation broke with finite values: silent
                # corruption of already-written cache rows, attributed
                # directly (the NaN case routes to the logits guard)
                TRACE_COUNTS[("abft", "kv_trip")] += 1
                self._note_sdc()
                self.completions.append(self.sched.retire(
                    slot, "sdc_detected", float(self.step)))
                self._abft_rebase_slot(slot)
                continue
            if ok_h is not None and not bool(ok_h[slot]):
                # logits-level trip: NaN from a numeric event OR the
                # kernel checksum's NaN-poisoned rows. With ABFT on,
                # attribute by re-verifying the weight checksums.
                reason = "nan_guard"
                if self._abft and self._weights_corrupt():
                    reason = "sdc_detected"
                    self._note_sdc()
                else:
                    self.sched.counters["guard_trips"] += 1
                    TRACE_COUNTS[("serving", "guard_trip")] += 1
                self.completions.append(self.sched.retire(
                    slot, reason, float(self.step)))
                if self._abft:
                    self._abft_rebase_slot(slot)
                continue
            tok = int(new_tok_h[slot, 0])
            st.generated.append(tok)
            st.pos += 1
            self.tokens_h[slot, 0] = tok
            self.positions_h[slot] = st.pos
            self._maybe_retire(slot, tok)

    def _watchdog(self, dt_ms: float) -> None:
        """Post-hoc step watchdog: a synchronous jit dispatch cannot be
        preempted, so the bound is checked after the fact (the slow
        step's result is still valid and used). Two CONSECUTIVE trips
        mean sustained sickness, not a scheduling blip -> degrade."""
        if self._watchdog_ms is None:
            return
        if self._watchdog_skip > 0:      # first step after a re-warm
            self._watchdog_skip -= 1     # compiles; not a hang
            return
        if dt_ms <= self._watchdog_ms:
            self._consec_slow = 0
            return
        self._consec_slow += 1
        self.sched.counters["watchdog_trips"] += 1
        TRACE_COUNTS[("serving", "watchdog_trip")] += 1
        if self._consec_slow >= 2:
            self._consec_slow = 0
            self._degrade(
                f"watchdog: 2 consecutive steps over "
                f"{self._watchdog_ms} ms")

    # ------------------------------------------------------ observability
    def decode_cache_size(self) -> int:
        """Total compiled decode executables across every rung bound so
        far -- 1 in steady state (fixed shapes, host-side scheduling),
        +1 per degradation re-warm and nothing else."""
        return sum(j._cache_size() for j in self._decode_jits)

    def quantize_weight_calls_during_serve(self) -> int:
        """quantize_weight invocations since warmup -- 0 on the prequant
        path (QTensor weights are consumed directly)."""
        return wquant.QUANTIZE_WEIGHT_CALLS - self._qw_calls_baseline

    def health(self) -> Dict[str, int]:
        """Structured robustness snapshot: the degradation / watchdog /
        numeric-guard / ABFT counters for THIS engine. TRACE_COUNTS keys
        are process-global, so they were snapshotted at construction and
        are reported here as deltas; scheduler counters are already
        per-engine."""
        delta = {k: int(TRACE_COUNTS[k] - self._trace_base[k])
                 for k in _HEALTH_TRACE_KEYS}
        return {
            "abft_enabled": int(self._abft),
            "guards_enabled": int(self._guard),
            "rung": int(self._rung),
            "degrades": int(self.sched.counters.get("degrades", 0)),
            "watchdog_trips": int(
                self.sched.counters.get("watchdog_trips", 0)),
            "step_retries": int(self.sched.counters.get("step_retries", 0)),
            "deadline_retired": int(
                self.sched.counters.get("deadline_retired", 0)),
            "nan_guard_trips": int(
                self.sched.counters.get("guard_trips", 0)),
            "sdc_retired": int(self.sched.counters.get("sdc_retired", 0)),
            "abft_kv_trips": delta[("abft", "kv_trip")],
            "abft_sdc_detections": delta[("abft", "sdc_detected")],
            "abft_params_checks": delta[("abft", "params_check")],
        }

    def summary(self) -> Dict[str, Any]:
        """Counters and rates of the engine's life so far. ``p50_token_ms``
        and ``p99_token_ms`` are decode-step times (each step emits one
        token per occupied slot); ``tokens_per_s`` is every generated
        token over the wall time ``run()`` served, admissions included."""
        steps = np.asarray(self._step_latencies_ms or [0.0])
        gen = sum(len(c.tokens) for c in self.completions)
        by_status: Dict[str, int] = {}
        for c in self.completions:
            by_status[c.status] = by_status.get(c.status, 0) + 1
        return {
            "requests": len(self.completions),
            "generated_tokens": gen,
            "decode_steps": len(self._step_latencies_ms),
            "idle_steps": self._idle_steps,
            "tokens_per_s": gen / self._serve_s if self._serve_s else 0.0,
            "occupancy": float(np.mean(self._occupancy)) if self._occupancy
            else 0.0,
            "p50_token_ms": float(np.percentile(steps, 50)),
            "p99_token_ms": float(np.percentile(steps, 99)),
            "compile_s": self._compile_s or 0.0,
            "decode_s": sum(self._step_latencies_ms) * 1e-3,
            "decode_executables": self.decode_cache_size(),
            "quantize_weight_calls": self.quantize_weight_calls_during_serve(),
            "kv_cache_bytes": cache_bytes(self.cfg, self.sched.num_slots,
                                          self.max_len),
            "rung": self._rung,
            "guards_enabled": int(self._guard),
            "abft_enabled": int(self._abft),
            "health": self.health(),
            **{f"status_{k}": v for k, v in sorted(by_status.items())},
            **{k: int(v) for k, v in self.sched.counters.items()},
        }
