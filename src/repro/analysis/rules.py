"""The kernel contract rules.

Each rule is a class decorated with :func:`register_rule` (the same
instantiate-into-a-dict idiom as ``kernels/registry.py``'s backend
registry): ``applies(site)`` keys off what the :class:`~.sites.Site`
carries, ``check(site)`` returns the violations. :func:`run_rules`
drives every registered rule over every site into a
:class:`~.report.Report`.

What each rule proves:

* ``fusion-contract``    -- a bound kernel site is ONE ``pallas_call``
  with no contraction escaping it, and serving traces never call
  ``quantize_weight``.
* ``rotate-once-contract`` -- the transform's pass matmuls live only
  under the ``j == 0`` cond; exactly one top-level contraction.
* ``dma-safety``         -- the streamed ring warms up before it waits,
  every start is guarded (so the ring drains at region end), and no
  start is left unmatched by a wait.
* ``dtype-flow``         -- 16-bit pass compute never silently upcasts
  to f32, and decode never materializes a cache-shaped dequantized
  (wider-than-io) tensor.
* ``vmem-budget``        -- the kernel's VMEM residents, re-charged
  from the jaxpr's memory spaces, fit the planner's decision and the
  device limit.
* ``donation``           -- donated serving executables actually alias
  their cache buffers in the compiled HLO (no defensive copy).
* ``scan-cache-xs``      -- no layer scan in a serving trace takes a KV
  cache stack as xs or returns one as ys (a whole-cache copy a step).
* ``deprecated-shim-in-trace`` -- no site traces through the
  deprecated ``kernels.ops`` / ``kernels.fused_quant`` shims.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from repro.analysis import jaxpr_utils as ju
from repro.analysis.report import Report, Violation
from repro.analysis.sites import Site

__all__ = ["Rule", "register_rule", "all_rules", "run_rules",
           "DEVICE_VMEM_BYTES"]

# per-core VMEM capacity the static re-charge is held under (16 MiB --
# the common floor across TPU generations; the planner's own working
# budget in kernels/registry.py is half this)
DEVICE_VMEM_BYTES = 16 * 1024 * 1024

_RULES: Dict[str, "Rule"] = {}


def register_rule(cls):
    """Class decorator: instantiate and register (mirrors
    ``kernels.registry.register_backend``)."""
    inst = cls()
    _RULES[inst.name] = inst
    return cls


def all_rules() -> Dict[str, "Rule"]:
    return dict(_RULES)


class Rule:
    """One invariant. ``applies`` gates on the facts the site carries;
    ``check`` returns Violations (empty == contract holds)."""

    name = "unnamed"

    def applies(self, site: Site) -> bool:
        raise NotImplementedError

    def check(self, site: Site) -> List[Violation]:
        raise NotImplementedError

    def _v(self, site: Site, msg: str) -> Violation:
        return Violation(rule=self.name, site=site.name, message=msg)


def run_rules(sites: Iterable[Site],
              rules: Optional[Iterable[str]] = None) -> Report:
    """Every (applicable) registered rule over every site."""
    picked = ([_RULES[r] for r in rules] if rules is not None
              else list(_RULES.values()))
    rep = Report()
    for site in sites:
        for rule in picked:
            if not rule.applies(site):
                continue
            rep.checked.append((site.name, rule.name))
            rep.violations.extend(rule.check(site))
    return rep


# --------------------------------------------------------------- fusion
@register_rule
class FusionContract(Rule):
    """Bound kernel/model sites lower to exactly ONE pallas_call with
    zero contraction work escaping it; no trace (serving included)
    quantizes weights on the fly."""

    name = "fusion-contract"

    def applies(self, site: Site) -> bool:
        return site.jaxpr is not None

    def check(self, site: Site) -> List[Violation]:
        out = []
        if site.expect_fused and site.kind in ("kernel", "model"):
            n = ju.count_pallas_calls(site.jaxpr)
            if n != 1:
                out.append(self._v(
                    site, f"expected exactly 1 pallas_call, traced {n} "
                    "(rotate/quantize/GEMM split across kernels or fell "
                    "back to the unfused path)"))
        if site.kind == "kernel":
            esc = ju.dots_outside_pallas(site.jaxpr)
            if esc:
                out.append(self._v(
                    site, f"{esc} dot_general(s) outside the pallas_call "
                    "-- contraction work escaped the fused kernel"))
        if site.kind == "serving" and site.qw_calls:
            out.append(self._v(
                site, f"{site.qw_calls} quantize_weight call(s) in a "
                "serving trace -- serving weights must be pre-quantized "
                "QTensors, never re-quantized per step"))
        return out


# ---------------------------------------------------------- rotate-once
@register_rule
class RotateOnceContract(Rule):
    """The transform's pass matmuls run only under the ``j == 0`` cond
    (once per row block) and exactly one top-level contraction runs per
    out-channel tile."""

    name = "rotate-once-contract"

    def applies(self, site: Site) -> bool:
        return (site.kind == "kernel" and site.plan is not None
                and site.schedule in ("rotate_once", "streamed"))

    def check(self, site: Site) -> List[Violation]:
        kernels = ju.kernel_jaxprs(site.jaxpr)
        if len(kernels) != 1:
            return [self._v(site, f"expected one kernel body, found "
                            f"{len(kernels)}")]
        top, in_cond = ju.dots_by_region(kernels[0])
        want = (1, site.plan.num_passes)
        if (top, in_cond) == want:
            return []
        return [self._v(
            site, f"(top-level dots, in-cond dots) = ({top}, {in_cond}), "
            f"expected {want} -- transform matmuls must sit under the "
            "j == 0 guard with a single top-level contraction "
            "(unguarded rotate re-transforms every revisit)")]


# ----------------------------------------------------------- DMA safety
@register_rule
class DmaSafety(Rule):
    """The streamed two-slot ring: warm-up + prefetch starts precede
    the first wait, both ring waits precede the single contraction, no
    start after the contraction (the ring drains at region end), every
    start guarded by a cond, and no start left without any wait."""

    name = "dma-safety"

    def applies(self, site: Site) -> bool:
        return site.kind == "kernel" and site.schedule == "streamed"

    def check(self, site: Site) -> List[Violation]:
        kernels = ju.kernel_jaxprs(site.jaxpr)
        if len(kernels) != 1:
            return [self._v(site, f"expected one kernel body, found "
                            f"{len(kernels)}")]
        kj = kernels[0]
        out = []
        starts = sum(1 for e in ju.iter_eqns(kj)
                     if e.primitive.name == "dma_start")
        waits = sum(1 for e in ju.iter_eqns(kj)
                    if e.primitive.name == "dma_wait")
        if starts == 0:
            return [self._v(site, "streamed kernel issues no dma_start -- "
                            "the ring is gone")]
        if waits == 0:
            out.append(self._v(
                site, f"{starts} dma_start(s) with NO dma_wait -- "
                "unmatched starts race the contraction"))
        unguarded = sum(1 for e in kj.eqns
                        if e.primitive.name == "dma_start")
        if unguarded:
            out.append(self._v(
                site, f"{unguarded} unguarded top-level dma_start(s) -- "
                "an unconditional start fires on EVERY grid step, so a "
                "copy is in flight when the row block's j loop ends "
                "(the ring never drains)"))
        events = ju.stream_events(kj)
        if events.count("dot") != 1:
            out.append(self._v(
                site, f"{events.count('dot')} top-level contractions in "
                "the streamed body, expected exactly 1"))
            return out
        dot_at = events.index("dot")
        if "wait" in events:
            first_wait = events.index("wait")
            if events[:first_wait].count("start_cond") < 2:
                out.append(self._v(
                    site, "fewer than 2 guarded copy-starts before the "
                    "first wait -- the j+1 prefetch must be in flight "
                    "before the kernel blocks on slot j (event order: "
                    f"{events})"))
            if events[first_wait:dot_at].count("wait") < 2:
                out.append(self._v(
                    site, "fewer than 2 waits before the contraction -- "
                    "the weight AND scale slots must both be settled "
                    f"(event order: {events})"))
        if "start_cond" in events[dot_at:]:
            out.append(self._v(
                site, "copy-start after the contraction -- the prefetch "
                "must precede the wait/dot so the overlap window exists "
                f"and the ring drains (event order: {events})"))
        return out


# ----------------------------------------------------------- dtype flow
@register_rule
class DtypeFlow(Rule):
    """16-bit pass compute stays 16-bit inside the kernel's transform
    cond (no silent f32 upcast of the pass matmuls), and decode traces
    never materialize a cache-shaped tensor WIDER than the io dtype
    (a dequantized KV cache copy would double decode bandwidth)."""

    name = "dtype-flow"

    def applies(self, site: Site) -> bool:
        return site.jaxpr is not None and (
            site.kind == "kernel" or
            (site.kind == "serving" and bool(site.cache_leaves)))

    def check(self, site: Site) -> List[Violation]:
        import jax.numpy as jnp

        out = []
        if site.kind == "kernel" and site.plan is not None:
            cd = jnp.dtype(site.plan.compute_dtype)
            if cd.itemsize == 2:
                for kj in ju.kernel_jaxprs(site.jaxpr):
                    for q in ju.guarded_eqns(kj):
                        if q.primitive.name != "dot_general":
                            continue
                        dts = {q.invars[0].aval.dtype,
                               q.invars[1].aval.dtype}
                        wide = [str(d) for d in dts
                                if jnp.dtype(d).itemsize > 2]
                        if wide:
                            out.append(self._v(
                                site, "transform pass matmul has "
                                f"{wide} operand(s) under a "
                                f"{cd.name} compute plan -- "
                                "silent f32 upcast of the pass compute"))
        if site.kind == "serving" and site.cache_leaves:
            cache_shapes = {tuple(s) for s, _ in site.cache_leaves}
            io = jnp.dtype(site.io_dtype)
            for e in ju.iter_eqns(site.jaxpr):
                if e.primitive.name not in ("convert_element_type", "mul"):
                    continue
                if len(e.outvars) != 1:
                    continue
                aval = e.outvars[0].aval
                shape = tuple(getattr(aval, "shape", ()))
                dt = getattr(aval, "dtype", None)
                if (shape in cache_shapes and dt is not None
                        and jnp.issubdtype(dt, jnp.floating)
                        and jnp.dtype(dt).itemsize > io.itemsize):
                    out.append(self._v(
                        site, f"cache-shaped {shape} tensor materialized "
                        f"as {jnp.dtype(dt).name} (> io dtype {io.name}) "
                        f"by {e.primitive.name} -- dequantized cache "
                        "copy in the decode trace"))
        return out


# ---------------------------------------------------------- VMEM budget
@register_rule
class VmemBudget(Rule):
    """Re-charge the kernel's VMEM residents straight from the jaxpr's
    ref memory spaces (operand/output tiles + scratch + DMA rings; ANY
    refs live in HBM, semaphores in the register file) and hold them
    against (a) the planner's own budget, (b) the device limit, and
    (c) the ``BlockDecision.vmem_bytes`` the planner charged -- a
    kernel edit that grows a resident the planner doesn't know about
    fails (c) before it OOMs on hardware."""

    name = "vmem-budget"

    def applies(self, site: Site) -> bool:
        return (site.kind == "kernel" and site.decision is not None
                and site.plan is not None)

    @staticmethod
    def _ref_bytes(aval) -> int:
        import math

        import jax.numpy as jnp

        return math.prod(aval.shape) * jnp.dtype(aval.dtype).itemsize

    def check(self, site: Site) -> List[Violation]:
        from repro.kernels.registry import _VMEM_BUDGET_BYTES, _plan_mats

        kernels = ju.kernel_jaxprs(site.jaxpr)
        if len(kernels) != 1:
            return [self._v(site, f"expected one kernel body, found "
                            f"{len(kernels)}")]
        out = []
        dec = site.decision
        if dec.vmem_bytes > _VMEM_BUDGET_BYTES:
            out.append(self._v(
                site, f"planner charged {dec.vmem_bytes} B, over its own "
                f"{_VMEM_BUDGET_BYTES} B budget"))
        mats_shape = tuple(_plan_mats(site.plan).shape)
        total = 0
        tiles = 0
        for v in kernels[0].invars:
            aval = v.aval
            if not hasattr(aval, "memory_space"):
                continue
            ms = aval.memory_space
            ms_name = "vmem_block" if ms is None else str(ms).lower()
            if "any" in ms_name or "semaphore" in ms_name:
                continue  # HBM-resident ref / register-file semaphore
            b = self._ref_bytes(aval)
            total += b
            if tuple(aval.shape) != mats_shape:
                tiles += b  # the planner charges tiles, not the mats
        if total > DEVICE_VMEM_BYTES:
            out.append(self._v(
                site, f"kernel refs charge {total} B of VMEM, over the "
                f"{DEVICE_VMEM_BYTES} B device limit"))
        if tiles > dec.vmem_bytes:
            out.append(self._v(
                site, f"jaxpr re-charge of operand/scratch/ring tiles = "
                f"{tiles} B exceeds the planner's BlockDecision."
                f"vmem_bytes = {dec.vmem_bytes} B -- a VMEM resident "
                "the planner never charged"))
        return out


# ------------------------------------------------------------- donation
@register_rule
class Donation(Rule):
    """Serving executables compiled with donated caches must alias a
    buffer per cache leaf in the compiled HLO (``input_output_alias``)
    and must not defensively ``copy`` any cache-shaped buffer -- either
    failure means a fresh cache allocation every step."""

    name = "donation"

    def applies(self, site: Site) -> bool:
        return (site.kind == "serving" and site.donated
                and site.hlo_text is not None and bool(site.cache_leaves))

    def check(self, site: Site) -> List[Violation]:
        from repro.launch.hlo_analysis import (_shape_dims, parse_hlo,
                                               parse_input_output_aliases)

        out = []
        aliases = parse_input_output_aliases(site.hlo_text)
        n_cache = len(site.cache_leaves)
        if len(aliases) < n_cache:
            out.append(self._v(
                site, f"compiled HLO aliases {len(aliases)} output "
                f"buffer(s) but the cache pytree has {n_cache} leaves "
                "-- donation was dropped (fresh cache allocation every "
                "step)"))
        cache_dims = {tuple(s) for s, _ in site.cache_leaves}
        comps = parse_hlo(site.hlo_text)
        entry = comps.get("__entry__")
        if entry is not None:
            for ins in entry.instrs:
                if ins.opcode != "copy" or not ins.operands:
                    continue
                dims, _ = _shape_dims(ins.shape_str)
                if tuple(dims) not in cache_dims:
                    continue
                # a DEFENSIVE copy duplicates the donated input itself
                # (the param, or a get-tuple-element of it). Copies of
                # loop results into output buffers are how CPU XLA
                # plumbs while-carried state -- aliasing, asserted
                # above, is the donation signal there.
                src = ins.operands[0]
                producer = entry.by_name.get(src)
                if producer is not None and producer.opcode == \
                        "get-tuple-element" and producer.operands:
                    src, producer = producer.operands[0], \
                        entry.by_name.get(producer.operands[0])
                if src in entry.params or (
                        producer is not None
                        and producer.opcode == "parameter"):
                    out.append(self._v(
                        site, f"defensive copy of the donated cache "
                        f"input ({ins.shape_str}) in the entry "
                        "computation -- the buffer is duplicated "
                        "instead of updated in place"))
        return out


# -------------------------------------------------------- scan-cache-xs
@register_rule
class ScanCacheXs(Rule):
    """A serving trace's layer scans take no stacked KV cache as xs or ys:
    a stack taken as the scan's xs is sliced into a per-layer copy, and
    one returned as ys is stacked back from the layers' copies -- each a
    whole-cache copy every decode step, where the step needs only its
    new rows written in place."""

    name = "scan-cache-xs"

    def applies(self, site: Site) -> bool:
        return (site.kind == "serving" and site.jaxpr is not None
                and bool(site.kv_leaves))

    def check(self, site: Site) -> List[Violation]:
        kv = {tuple(s) for s in site.kv_leaves}
        return [self._v(
            site, f"a scan takes the KV cache stack {tuple(aval.shape)} as "
            f"{role} -- every layer's cache is copied out and stacked "
            "back each step instead of read in place and written by row")
            for role, aval in ju.scan_xs_ys(site.jaxpr)
            if tuple(aval.shape) in kv]


# ----------------------------------------------------- deprecated shims
@register_rule
class DeprecatedShim(Rule):
    """No lint site traces through the deprecated ``kernels.ops`` /
    ``kernels.fused_quant`` shims -- new code importing them fails the
    lint leg instead of warning once at runtime."""

    name = "deprecated-shim-in-trace"

    def applies(self, site: Site) -> bool:
        return bool(site.shim_calls)

    def check(self, site: Site) -> List[Violation]:
        return [self._v(
            site, f"deprecated shim {shim} called {n}x during trace -- "
            "route through the plan API (core.api.hadamard / "
            "online_hadamard_quantize) instead")
            for shim, n in sorted(site.shim_calls.items()) if n]
