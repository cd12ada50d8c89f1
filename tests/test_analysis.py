"""The kernel contract linter (PR 9): shared jaxpr walkers, the rule
registry, every rule against healthy sites, the mutation fixture (the
rules must flag the committed broken kernels), and the CLI."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Report, Violation, all_rules,
                            count_pallas_calls, dots_by_region,
                            dots_outside_pallas, kernel_jaxpr,
                            kernel_sites, model_sites, run_rules,
                            stream_events)
from repro.analysis.mutations import mutant_sites
from repro.analysis.sites import Site

EXPECTED_RULES = {"fusion-contract", "rotate-once-contract", "dma-safety",
                  "dtype-flow", "vmem-budget", "donation",
                  "deprecated-shim-in-trace"}


# ------------------------------------------------------------ registry
def test_rule_registry_carries_every_contract():
    assert EXPECTED_RULES <= set(all_rules())


def test_register_rule_is_open():
    from repro.analysis.rules import _RULES, Rule, register_rule

    @register_rule
    class _Probe(Rule):
        name = "probe-rule"

        def applies(self, site):
            return True

        def check(self, site):
            return [self._v(site, "probed")]

    try:
        rep = run_rules([Site(name="s", kind="kernel")],
                        rules=["probe-rule"])
        assert [v.rule for v in rep.violations] == ["probe-rule"]
        assert rep.checked == [("s", "probe-rule")]
    finally:
        del _RULES["probe-rule"]


# ------------------------------------------------------- report model
def test_report_round_trips_json():
    rep = Report(checked=[("s", "r")],
                 violations=[Violation("r", "s", "broken")])
    d = json.loads(rep.to_json())
    assert d["ok"] is False and d["violations"][0]["rule"] == "r"
    assert not rep.ok and "broken" in rep.format_text()
    clean = Report(checked=[("s", "r")])
    assert clean.ok and json.loads(clean.to_json())["ok"] is True


# ---------------------------------------------------- shared walkers
def test_walkers_see_through_pjit_and_cond():
    def f(x):
        return jax.jit(lambda a: jax.lax.cond(
            a.sum() > 0, lambda b: b @ b, lambda b: b + b, a))(x)

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4)))
    assert count_pallas_calls(jaxpr) == 0
    assert dots_outside_pallas(jaxpr) == 1  # the cond-branch matmul
    with pytest.raises(AssertionError):
        kernel_jaxpr(jaxpr)


# ------------------------------------------------- rules on main
@pytest.mark.parametrize("schedule", ["rotate_once", "streamed"])
def test_kernel_sites_lint_clean(schedule):
    """Main's 2-D and 3-D fused kernels pass every rule, and the
    expected rules actually RAN (not vacuously skipped)."""
    sites = kernel_sites("llama3_8b", schedule)
    rep = run_rules(sites)
    assert rep.ok, rep.format_text()
    ran = {r for _, r in rep.checked}
    want = {"fusion-contract", "rotate-once-contract", "vmem-budget",
            "dtype-flow"}
    if schedule == "streamed":
        want.add("dma-safety")
    assert want <= ran
    # and the structural facts the rules checked are the known ones
    kj = kernel_jaxpr(sites[0].jaxpr)
    assert dots_by_region(kj) == (1, sites[0].plan.num_passes)
    if schedule == "streamed":
        assert stream_events(kj).count("dot") == 1


@pytest.mark.parametrize("schedule", ["rotate_once", "streamed"])
def test_w_down_sites_lint_clean(schedule):
    """Phi-4-mini's fp8 w_down (n 8192, d 3072) at a decode batch and a
    prefill bucket: the chunked rotation stays under the j == 0 guard,
    the kernel is one pallas_call, and its refs fit what the planner
    charged and the budget."""
    from repro.analysis.sites import w_down_sites

    sites = w_down_sites(schedule)
    assert [s.decision.block_m for s in sites] == [32, 64]
    rep = run_rules(sites, rules=["vmem-budget", "rotate-once-contract",
                                  "fusion-contract"])
    assert rep.ok, rep.format_text()
    assert len(rep.checked) == 3 * len(sites)


def test_model_site_lints_clean():
    rep = run_rules(model_sites("llama3_8b"))
    assert rep.ok, rep.format_text()


# ------------------------------------------------- mutation fixture
def test_mutants_are_flagged():
    """The committed broken kernels MUST fail the lint -- the unguarded
    rotate trips rotate-once-contract, the dangling DMA trips
    dma-safety (unmatched start + unguarded start)."""
    sites = mutant_sites()
    rep = run_rules(sites)
    by_site = {}
    for v in rep.violations:
        by_site.setdefault(v.site, set()).add(v.rule)
    assert "rotate-once-contract" in by_site.get(
        "mutant[unguarded_rotate]", set())
    assert "dma-safety" in by_site.get("mutant[dangling_dma]", set())
    msgs = " ".join(v.message for v in rep.violations
                    if v.site == "mutant[dangling_dma]")
    assert "NO dma_wait" in msgs and "unguarded" in msgs


def test_vmem_rule_has_teeth():
    """An inflated BlockDecision charge is NOT flagged (planner may
    over-charge), but a decision claiming fewer bytes than the jaxpr's
    VMEM residents is."""
    from repro.kernels.quant_dot import BlockDecision

    site = kernel_sites("llama3_8b", "rotate_once")[0]
    dec = site.decision
    site.decision = BlockDecision(dec.block_m, dec.block_n, dec.schedule,
                                  64)
    rep = run_rules([site], rules=["vmem-budget"])
    assert not rep.ok
    assert "vmem_bytes" in rep.violations[0].message


def test_dtype_flow_flags_cache_dequant():
    """A decode-shaped trace that materializes the cache as f32 (wider
    than the bf16 io dtype) is flagged; the io-dtype convert the real
    attention path performs is not."""
    cache = jnp.zeros((2, 8, 1, 16), jnp.float8_e4m3fn)

    def bad(c):
        return c.astype(jnp.float32) * 2.0

    def good(c):
        # the real decode path: convert to the io dtype, never wider
        return c.astype(jnp.bfloat16) * jnp.bfloat16(2)

    leaves = ((tuple(cache.shape), str(cache.dtype)),)
    mk = lambda fn: Site(name="t", kind="serving",
                         jaxpr=jax.make_jaxpr(fn)(cache),
                         io_dtype=jnp.dtype(jnp.bfloat16),
                         cache_leaves=leaves)
    assert not run_rules([mk(bad)], rules=["dtype-flow"]).ok
    assert run_rules([mk(good)], rules=["dtype-flow"]).ok


def test_deprecated_shim_rule_fires_on_shim_trace():
    from repro.analysis.sites import traced
    from repro.kernels.fused_quant import fused_hadamard_quantize

    jaxpr, qw, shim = traced(fused_hadamard_quantize,
                             jnp.ones((4, 64), jnp.float32))
    site = Site(name="shimmed", kind="model", jaxpr=jaxpr,
                qw_calls=qw, shim_calls=shim, expect_fused=False)
    rep = run_rules([site], rules=["deprecated-shim-in-trace"])
    assert not rep.ok and "fused_quant" in rep.violations[0].message


# ----------------------------------------------------------- CLI
def test_cli_mutation_mode_exits_nonzero(tmp_path):
    from repro.analysis.lint import main

    out = tmp_path / "mut.json"
    rc = main(["--mutation", "--json", str(out)])
    assert rc != 0
    d = json.loads(out.read_text())
    flagged = {v["site"] for v in d["violations"]}
    assert {"mutant[unguarded_rotate]", "mutant[dangling_dma]"} <= flagged


def test_cli_kernel_sites_pass_and_list_rules(tmp_path, capsys):
    from repro.analysis.lint import main

    assert main(["--list-rules"]) == 0
    assert "dma-safety" in capsys.readouterr().out
    out = tmp_path / "lint.json"
    rc = main(["--config", "llama3_8b", "--schedule", "streamed",
               "--no-serving", "--json", str(out)])
    assert rc == 0
    d = json.loads(out.read_text())
    assert d["ok"] is True and len(d["checked"]) > 0
    assert main(["--rule", "not-a-rule"]) == 2
