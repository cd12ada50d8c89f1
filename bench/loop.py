"""The open loop: requests go to the engine when they are due on the
wall clock, whether or not earlier ones have finished.

It drives ``ServeEngine`` through its scheduler and its own admission
(prefill + insert, blocking) and decode-step entry points, in the order
``ServeEngine.run`` uses: every due request a free slot can take, then
one decode step over all slots. The harness stamps each request at its
due time, each admission and decode step with the host clock, and each
token when the host holds it. Each admission and decode step, and each
wait for an arrival, is also a ``TraceAnnotation`` (``bench.admit``,
``bench.decode``, ``bench.wait``), so that a profiler trace can say
what the host was doing while the device was idle.

The window opens at the start of the loop, or (``"opens":
"slots_full"``) once every slot has first been filled, and closes
``seconds`` later. Where the mix asks for a drain, requests due in the
window that have not been admitted by the close are served on, up to
``drain_limit_s``, so that each has its first token and its time to
first token counts the wait; nothing due after the close is sent."""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    rid: int
    due: float                      # absolute host time
    prompt: np.ndarray
    max_new: int
    admit: Optional[tuple] = None   # (start, end) host times
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: tuple = ()
    status: Optional[str] = None    # Completion.status once retired


@dataclasses.dataclass
class Log:
    records: Dict[int, Record]
    admits: List[tuple]             # (start, end, prompt_len)
    decodes: List[tuple]            # (start, end, [depth of each slot])
    t0: float
    open: float
    close: float
    end: float                      # after the drain


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def warm(engine) -> None:
    """Run the loop's entry points once on a throwaway request, so that
    the first admission in the window compiles nothing (reading the
    first token off the device is a program of its own)."""
    from repro.serving.scheduler import Request

    engine.submit(Request(rid=-1, tokens=np.zeros(1, np.int32),
                          max_new_tokens=2, arrival_time=0.0))
    engine._admit(*engine.sched.next_admission(0.0))
    engine._decode_step()
    if engine.sched.active or engine.sched.queue:
        raise RuntimeError("the warm-up request did not retire")
    engine.completions.clear()


def drive(engine, planned, seconds: float, window: dict,
          drain_limit_s: float = 0.0,
          mark: Optional[Callable[[str], None]] = None) -> Log:
    from repro.serving.scheduler import Request

    sched = engine.sched
    mark = mark or (lambda name: None)
    log = Log({}, [], [], 0.0, float("inf"), float("inf"), float("inf"))

    def open_window():
        mark("window_open")          # a traced run starts its profiler here
        with _annotate("bench.window_open"):
            log.open = time.perf_counter()
        log.close = log.open + seconds

    if window["opens"] == "start":
        open_window()
    elif window["opens"] != "slots_full":
        raise ValueError(f"unknown window rule {window['opens']!r}")
    t0 = log.t0 = min(log.open, time.perf_counter())
    recs = log.records
    for p in planned:
        recs[p.rid] = Record(p.rid, t0 + p.due_s, p.tokens, p.max_new_tokens)
        engine.submit(Request(rid=p.rid, tokens=p.tokens,
                              max_new_tokens=p.max_new_tokens,
                              arrival_time=p.due_s))
    draining = False
    while True:
        now = time.perf_counter()
        if now >= log.close and not draining:
            with _annotate("bench.window_close"):
                mark("window_close")
            draining = True
            # nothing due after the close is sent
            late = log.close - t0
            sched.queue = collections.deque(
                r for r in sched.queue if r.arrival_time < late)
        if draining and (not sched.queue
                         or now >= log.close + drain_limit_s
                         or drain_limit_s <= 0):
            break
        while (adm := sched.next_admission(now - t0)) is not None:
            slot, req = adm
            a = time.perf_counter()
            with _annotate("bench.admit"):
                engine._admit(slot, req)
            b = time.perf_counter()
            recs[req.rid].admit = (a, b)
            recs[req.rid].token_times.append(b)
            log.admits.append((a, b, req.prompt_len))
            now = b
        if log.open == float("inf") and len(sched.active) == sched.num_slots:
            open_window()
        if sched.active:
            rids = [st.rid for st in sched.active.values()]
            depths = [st.pos for st in sched.active.values()]
            a = time.perf_counter()
            with _annotate("bench.decode"):
                engine._decode_step()
            b = time.perf_counter()
            for rid in rids:
                recs[rid].token_times.append(b)
            log.decodes.append((a, b, depths))
            continue
        nxt = sched.next_arrival()
        until = log.close if nxt is None else min(t0 + nxt, log.close)
        if until == float("inf"):
            raise RuntimeError("the window never opened: the mix has too "
                               "few requests to fill every slot")
        with _annotate("bench.wait"):
            time.sleep(max(0.0, until - time.perf_counter()))
    log.end = time.perf_counter()
    for c in engine.completions:
        if c.rid in recs:
            recs[c.rid].tokens, recs[c.rid].status = c.tokens, c.status
    return log
