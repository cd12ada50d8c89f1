"""Collectives, prefill: as ``collective.decode.exposed_pct`` over the
window's admissions (batch-1 prefill + insert) (%)."""
from bench.metrics_util import exposed_collective_pct


def read(run):
    return exposed_collective_pct(run, "prefill")
