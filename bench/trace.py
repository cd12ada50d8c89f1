"""From a profiler trace to numbers: device busy time and idle share,
kernel time by name and by the program it ran in, the device operations
that took most time, and the idle gaps by what the host was doing.

``read_xplane`` extracts what the reduction needs from the ``.xplane.pb``
that ``jax.profiler`` writes; everything after works on that extract (a
plain dict, so a small one is kept as a test fixture):

    {"device": {plane: [[name, start_ns, dur_ns], ...]},   # XLA Ops line
     "host": [[name, start_ns, dur_ns], ...]}   # the harness's spans

On a TPU the "XLA Ops" line names each operation by its HLO instruction,
shapes included, and nests a loop's body operations inside the loop's
own event: busy time is the union of the innermost operations, and an
operation's self time is its duration less its children's. Host and
device events share the profiler's clock. The window is the span
between the harness's ``bench.window_open`` and ``bench.window_close``
marks, ended early where the profiler stopped keeping a chip's events
(``Trace.kept_until``). A device operation belongs to the program whose harness span
(``bench.admit``: prefill and insert; ``bench.decode``: the decode
step) holds its start: the harness blocks on each program's result
inside its span, so the device runs that program's work there. The
profiler starts at the window's opening, so the n-th span of a kind in
the trace is the n-th admission or decode step that the harness's log
holds from the opening on (``step_of``)."""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
PROGRAM_OF_SPAN = {"bench.admit": "prefill", "bench.decode": "decode"}
HOST_DOING = {"bench.admit": "admission (prefill + insert)",
              "bench.decode": "decode step (dispatch + host sync)",
              "bench.wait": "waiting for arrivals"}
BETWEEN = "harness between steps"
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "bf16": 2, "f16": 2, "s16": 2, "f32": 4, "s32": 4, "u32": 4}


def read_xplane(path: str) -> dict:
    """The extract of one trace file: the operations of every TPU
    device plane, and the harness's ``bench.*`` spans on the host."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"][plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return out


def call_shapes(op_name: str):
    """(results, operands) of an HLO instruction's text, each a list of
    (dtype, dims): the shapes before the opcode's parenthesis, and those
    inside it. Layouts sit in braces and may hold parentheses too."""
    rhs = op_name.partition(" = ")[2]
    braces, start = 0, None
    for i, ch in enumerate(rhs):
        braces += {"{": 1, "}": -1}.get(ch, 0)
        if ch == "(" and braces == 0 and i and (rhs[i - 1].isalnum()
                                               or rhs[i - 1] in "-_"):
            start = i
            break
    if start is None:
        return [], []
    depth, end = 0, len(rhs)
    for end in range(start, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        if depth == 0:
            break
    dims = lambda sh: [(d, tuple(int(x) for x in s.split(",") if x))
                       for d, s in sh]
    return (dims(_SHAPE.findall(rhs[:start])),
            dims(_SHAPE.findall(rhs[start:end])))


def shape_bytes(shapes) -> int:
    total = 0
    for dtype, dims in shapes:
        n = DTYPE_BYTES[dtype]
        for d in dims:
            n *= d
        total += n
    return total


def _union(intervals: Iterable[Tuple[float, float]]):
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _nest(ops):
    """Sort one line's events and give each its self time and whether it
    is innermost: [name, start, dur, self, leaf]."""
    evs = [[n, s, d, d, True] for n, s, d in sorted(
        ops, key=lambda e: (e[1], -e[2]))]
    stack = []
    for e in evs:
        while stack and stack[-1][1] + stack[-1][2] <= e[1]:
            stack.pop()
        if stack:
            stack[-1][3] -= e[2]
            stack[-1][4] = False
        stack.append(e)
    return evs


class Trace:
    """The reduction of one extract over the harness's window."""

    def __init__(self, extract: dict):
        self.host = sorted(extract["host"], key=lambda e: e[1])
        marks = {e[0]: e[1] for e in self.host}
        if "bench.window_open" not in marks:
            raise ValueError("the trace holds no bench.window_open mark")
        self.t_open = marks["bench.window_open"]
        self.t_marked = marks.get("bench.window_close",
                                  max(e[1] + e[2] for e in self.host))
        self.chips = sorted(extract["device"])
        self.ops = {c: _nest(extract["device"][c]) for c in self.chips}
        spans = [e for e in self.host if e[0] in HOST_DOING]
        self._span_starts = [e[1] for e in spans]
        self._spans = spans
        seen: Dict[str, int] = {}
        self._ordinal: List[Optional[int]] = []
        for name, start, _ in spans:
            if start < self.t_open:
                self._ordinal.append(None)
                continue
            self._ordinal.append(seen.get(name, 0))
            seen[name] = seen.get(name, 0) + 1
        # the reduction's window: every reading below stops here
        self.t_close = min([self.t_marked]
                           + [self.kept_until(c) for c in self.chips])

    def kept_until(self, chip: str) -> float:
        """Where the profiler stopped keeping ``chip``'s operations. Each
        admission and decode step runs its program on every chip inside
        its span, so where the window's program spans from one on hold
        no operation of the chip, its events ended before that span's
        start; a plane with no operation in the window ended at the
        opening. The marked close where nothing was dropped. A device
        idle at the window's end, waiting for arrivals, holds no program
        span there and keeps its whole window."""
        last = max((e[1] for e in self.ops[chip]), default=None)
        if last is None or last < self.t_open:
            return self.t_open
        for name, start, _ in self._spans:
            if name in PROGRAM_OF_SPAN and last < start < self.t_marked:
                return start
        return self.t_marked

    @property
    def window_s(self) -> float:
        """The seconds of the window that the trace kept."""
        return (self.t_close - self.t_open) * 1e-9

    @property
    def marked_s(self) -> float:
        """The seconds between the harness's window marks."""
        return (self.t_marked - self.t_open) * 1e-9

    def _in_window(self, ops):
        return [e for e in ops if self.t_open <= e[1] < self.t_close]

    def busy_intervals(self, chip: str):
        lo, hi = self.t_open, self.t_close
        return _union((max(e[1], lo), min(e[1] + e[2], hi))
                      for e in self.ops[chip]
                      if e[4] and e[1] < hi and e[1] + e[2] > lo)

    def busy_s(self) -> float:
        """Seconds of the window in which an operation ran on the
        device, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(c))
                   for c in self.chips) * 1e-9 / len(self.chips)

    def _span_index(self, t: float) -> Optional[int]:
        i = bisect.bisect_right(self._span_starts, t) - 1
        if i >= 0 and t < self._spans[i][1] + self._spans[i][2]:
            return i
        return None

    def span_at(self, t: float) -> Optional[str]:
        """The harness span that holds host time ``t``, if any."""
        i = self._span_index(t)
        return None if i is None else self._spans[i][0]

    def program_of(self, op) -> Optional[str]:
        return PROGRAM_OF_SPAN.get(self.span_at(op[1]))

    def step_of(self, op) -> Optional[int]:
        """Which admission or decode step, counted from the window's
        opening within its kind, holds the start of operation ``op``."""
        i = self._span_index(op[1])
        return None if i is None else self._ordinal[i]

    def kernel_events(self, pattern: str, program: Optional[str] = None):
        """Window events of every chip whose instruction name matches
        ``pattern`` (a regular expression on the text before ' = '), in
        ``program`` where given."""
        rx = re.compile(pattern)
        return [e for c in self.chips for e in self._in_window(self.ops[c])
                if rx.search(e[0].partition(" = ")[0])
                and (program is None or self.program_of(e) == program)]

    def device_ops(self, top: int = 10):
        """The operations that took most device self time in the window,
        summed by program and instruction name."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            for e in self._in_window(self.ops[c]):
                key = f"{self.program_of(e) or 'other'}:" \
                      f"{e[0].partition(' = ')[0].lstrip('%')}"
                tot[key] = tot.get(key, 0.0) + e[3] * 1e-9 / len(self.chips)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, top: int = 10):
        """Idle device time in the window, summed by what the host was
        doing at the middle of each gap, longest first."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            t = self.t_open
            for a, b in self.busy_intervals(c) + [[self.t_close] * 2]:
                if a > t:
                    what = HOST_DOING.get(self.span_at((a + t) / 2), BETWEEN)
                    tot[what] = tot.get(what, 0.0) + (a - t) * 1e-9
                t = max(t, b)
        n = max(len(self.chips), 1)
        return sorted(([k, v / n] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]
