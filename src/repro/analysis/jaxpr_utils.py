"""Shared jaxpr walkers for the kernel contract linter (PR 9).

These started life as ad-hoc helpers copy-pasted across
``tests/test_plan_api.py`` and ``tests/test_quant_dot.py``; every
structural invariant the repo asserts -- one-pallas_call fusion, the
rotate-once cond signature, the streamed DMA-ring event order -- now
reads through this one module, so the tests and the ``repro.analysis``
rules literally share an implementation.

All walkers recurse through ``eqn.params.values()`` (``ClosedJaxpr`` /
``Jaxpr`` / list / tuple), which covers cond branches, scan/while
bodies, pjit calls and remat -- anywhere jax stashes a subjaxpr.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

from repro.jaxapi import ClosedJaxpr, Jaxpr

__all__ = [
    "as_jaxpr",
    "count_pallas_calls",
    "count_primitive",
    "dots_by_region",
    "dots_outside_pallas",
    "guarded_eqns",
    "iter_eqns",
    "kernel_jaxpr",
    "kernel_jaxprs",
    "pallas_call_eqns",
    "stream_events",
]


def as_jaxpr(j):
    """Unwrap a ``ClosedJaxpr`` to its ``Jaxpr`` (identity otherwise)."""
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def iter_eqns(jaxpr, *, into_pallas: bool = True) -> Iterator:
    """Yield every eqn in ``jaxpr`` and (recursively) every subjaxpr
    reachable through eqn params. ``into_pallas=False`` stops at
    ``pallas_call`` boundaries (the eqn itself is still yielded)."""

    def walk(v):
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            yield from scan(as_jaxpr(v))
        elif isinstance(v, (list, tuple)):
            for u in v:
                yield from walk(u)

    def scan(j):
        for eqn in j.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call" and not into_pallas:
                continue
            for param in eqn.params.values():
                yield from walk(param)

    yield from scan(as_jaxpr(jaxpr))


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` anywhere in ``jaxpr``."""
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` eqns anywhere in ``jaxpr`` -- the
    fusion contract asserts this is exactly 1 per bound kernel site."""
    return count_primitive(jaxpr, "pallas_call")


def pallas_call_eqns(jaxpr) -> List:
    """Every ``pallas_call`` eqn in ``jaxpr``, outermost-first."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def kernel_jaxprs(jaxpr) -> List[Jaxpr]:
    """The kernel-body jaxprs of every ``pallas_call`` in ``jaxpr``
    (``params["jaxpr"]`` is a raw ``Jaxpr``)."""
    return [e.params["jaxpr"] for e in pallas_call_eqns(jaxpr)]


def kernel_jaxpr(jaxpr) -> Jaxpr:
    """The kernel jaxpr of the single ``pallas_call`` inside ``jaxpr``;
    raises if the trace fused into anything other than exactly one."""
    found = kernel_jaxprs(jaxpr)
    if len(found) != 1:
        raise AssertionError(
            f"expected exactly one pallas_call, got {found}")
    return found[0]


def guarded_eqns(kjaxpr) -> Iterator:
    """Every eqn under the top-level conds of a kernel jaxpr (the
    ``pl.when`` regions), through the loops nested in them: a loop body
    appears once, however many times it runs."""
    for e in as_jaxpr(kjaxpr).eqns:
        if e.primitive.name == "cond":
            for br in e.params["branches"]:
                yield from iter_eqns(br)


def dots_by_region(kjaxpr) -> Tuple[int, int]:
    """(top-level dot_general count, dot_general count under cond
    branches) of a kernel jaxpr -- the structural signature of the
    rotate-once schedule: the transform's pass matmuls live under the
    ``j == 0`` cond (in the chunk loop's body where the row block is
    rotated in chunks), the contraction outside it."""
    kjaxpr = as_jaxpr(kjaxpr)
    top = sum(1 for e in kjaxpr.eqns if e.primitive.name == "dot_general")
    in_cond = sum(1 for e in guarded_eqns(kjaxpr)
                  if e.primitive.name == "dot_general")
    return top, in_cond


def dots_outside_pallas(jaxpr) -> int:
    """dot_general count anywhere in the jaxpr EXCEPT inside pallas_call
    kernel bodies -- nonzero means contraction work escaped the fused
    kernel (e.g. the einsum fallback ran)."""
    return sum(1 for e in iter_eqns(jaxpr, into_pallas=False)
               if e.primitive.name == "dot_general")


def stream_events(kjaxpr) -> List[str]:
    """Ordered top-level event list of a streamed kernel jaxpr:
    ``start_cond`` (a cond whose branch issues an async-copy start --
    the warm-up at j == 0 or the j+1 prefetch), ``wait`` (a top-level
    dma_wait), ``dot`` (a top-level dot_general, the contraction)."""

    def _has_dma_start(br):
        return any(q.primitive.name == "dma_start"
                   for q in as_jaxpr(br).eqns)

    events = []
    for e in as_jaxpr(kjaxpr).eqns:
        if e.primitive.name == "cond" and any(
                _has_dma_start(br) for br in e.params["branches"]):
            events.append("start_cond")
        elif e.primitive.name == "dma_wait":
            events.append("wait")
        elif e.primitive.name == "dot_general":
            events.append("dot")
    return events
