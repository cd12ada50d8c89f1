"""Collectives, decode: the share of the window's decode steps in which
a collective (all-gather, all-reduce, reduce-scatter, collective-permute,
all-to-all) runs on a chip and no other operation of that chip does,
averaged over the chips (%). Nothing on one chip or without a
collective."""
from bench.metrics_util import exposed_collective_pct


def read(run):
    return exposed_collective_pct(run, "decode")
