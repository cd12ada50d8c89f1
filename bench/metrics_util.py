"""What several metric readers share: a kernel's share of its roofline
in one program, from the trace's events, the harness's log and the work
counts."""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench.trace import call_shapes, shape_bytes
from bench.work import counts

# the instruction names the program's kernels take in a TPU trace
KERNEL_PATTERNS = {"quant_dot": r"_pallas_quant_dot\b",
                   "hadamard": r"_pallas_transform\b"}


def real_tokens(run, program: str) -> Tuple[List[int], int]:
    """The real tokens of each admission (its prompt) or decode step (its
    occupied slots) from the window's opening on, in the order the
    harness ran them, and the tokens the program is built for (the
    prefill bucket, or every slot)."""
    log = run.log
    if program == "prefill":
        return ([n for a, _, n in log.admits if a >= log.open],
                int(run.mix["prefill_len"]))
    return ([len(d) for a, _, d in log.decodes if a >= log.open],
            int(run.mix["slots"]))


def call_work(kernel: str, op_name: str, tokens: float, built_for: int,
              shards: int = 1):
    """(ops, bytes) of the useful work of one call, from the shapes in its
    instruction. A step built for ``built_for`` tokens, of which ``tokens``
    are real, hands the kernel g rows a token (the grouped transform takes
    a token's row as g groups). A mesh of ``shards`` devices splits them
    s ways (s = ``shards``, or 1 where it does not divide the rows), so a
    call holds g * built_for / s rows, which the kernel pads to its row
    blocks by fewer than built_for / shards. The call counts its share of
    the real rows, g * tokens / s = (rows * shards // built_for) * tokens
    / shards; on one device, rows // built_for * tokens. Operands and
    results that have the call's rows count the real rows alone; weights,
    scales and pass matrices count whole. Every operand is read once and
    every result written once."""
    results, operands = call_shapes(op_name)
    (_, x), (_, out) = operands[0], results[0]
    rows = x[0]
    real = rows * shards // built_for * tokens / shards

    def nbytes(shapes):
        return sum(shape_bytes([s]) * (real / rows if s[1][:1] == (rows,)
                                       else 1) for s in shapes)

    byt = nbytes(results) + nbytes(operands)
    if kernel == "quant_dot":
        return counts.quant_dot_ops(real, x[1], out[1]), byt
    return counts.hadamard_ops(real, x[1]), byt


def kernel_roofline_pct(run, kernel: str, program: str) -> Optional[float]:
    """The least time of the useful work of every call of ``kernel`` in
    ``program`` over their summed device time (%). Each call is held to
    its own chip's peaks, and the calls of every chip are summed."""
    if run.trace is None:
        return None
    events = run.trace.kernel_events(KERNEL_PATTERNS[kernel], program)
    busy = sum(e[2] for e in events) * 1e-9
    if not events or not busy:
        return None
    tokens, built_for = real_tokens(run, program)
    least = 0.0
    for e in events:
        ops, byt = call_work(kernel, e[0], tokens[run.trace.step_of(e)],
                             built_for, run.chips)
        least += counts.roofline_time(ops, byt, run.matmul_peak(),
                                      run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
