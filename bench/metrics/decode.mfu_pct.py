"""Model step, decode: the useful operations of the window's decode
steps (``work.counts.decode_flops`` of each occupied slot at its depth)
over their summed host time times the matmul peak of every chip the
cell uses (%)."""
from bench.work import counts


def read(run):
    steps = run.decodes()
    busy = sum(b - a for a, b, _ in steps)
    if not busy:
        return None
    ops = sum(counts.decode_flops(run.config, d)
              for _, _, depths in steps for d in depths)
    return 100.0 * ops / (busy * run.matmul_peak() * run.chips)
