"""Model step, prefill: the useful operations of the window's admissions
(``work.counts.prefill_flops`` of each real prompt) over their summed
host time times the peak the configuration's matmuls run at, on every
chip the cell uses (%)."""
from bench.work import counts


def read(run):
    adm = run.admits()
    busy = sum(b - a for a, b, _ in adm)
    if not busy:
        return None
    ops = sum(counts.prefill_flops(run.config, n) for _, _, n in adm)
    return 100.0 * ops / (busy * run.matmul_peak() * run.chips)
