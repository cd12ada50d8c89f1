"""The program's own spans and scopes in a profiler trace, beside the
harness's reduction (``bench.trace``), and a tool that runs one cell
traced and prints what they show.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

``read_program`` reads two keys more than ``bench.trace.read_xplane``
from the same ``.xplane.pb``; an extract that holds both reads as before
through ``Trace``, and ``ProgramTrace`` reads the two:

    {"program": [[name, start_ns, dur_ns], ...],    # engine.* host spans
     "scopes": {plane: [op-name path, ...]}}        # one per XLA Ops event

A program span's name is the text before its first ``#`` (keyword stats
never change it); the serving engine's spans (``serving/engine.py``)
nest, and a host instant belongs to the innermost span that holds it.
An operation's op-name path is the ``op_name`` metadata of its HLO
instruction, the ``jax.named_scope`` path the model gives it
(``jit(wrapped)/layers/while/body/closed_call/attention/...``). A TPU
trace names each event by its instruction's text alone; the path comes
from the HLO module protos that the file carries on its
``/host:metadata`` plane, for the module that the device's
``XLA Modules`` line shows running at the operation's start. XLA's own
copies and loop plumbing carry no path: "".

The four readings (``READINGS``) are what ``bench/run.py`` does not
report: device idle in the window while the host dispatches a decode
step, and while it reads the step back and does its bookkeeping; and
the decode program's device self time under ``attention`` and under no
model scope (the layer scan), per decode step."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterable, List, Optional  # noqa: E402

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.trace import OPS_LINE, Trace  # noqa: E402

METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_PROTO_STAT = "Hlo Proto"
SCOPE_CLASSES = ("attention", "mlp", "embed", "logits")
NO_SCOPE = "layer scan"
DISPATCH = ("engine.decode.dispatch",)
POST_STEP = ("engine.decode.readback", "engine.decode.bookkeep")


# ------------------------------------------------------- protobuf wire
def _fields(buf: bytes):
    """(field number, value) of each field of one serialized protobuf
    message: an int for varints, bytes otherwise."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            v = varint()
        elif kind == 2:
            size = varint()
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _first(buf: bytes, field: int):
    """The first value of ``field``, parsing no further than it: a
    plane's name (2) comes before its lines."""
    return next((v for f, v in _fields(buf) if f == field), None)


def _map_values(plane: bytes, field: int) -> Iterable[bytes]:
    """The values of one map field of an XPlane (entries: key 1, value 2)."""
    for f, entry in _fields(plane):
        if f == field:
            for g, v in _fields(entry):
                if g == 2:
                    yield v


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op_name}} of every HLO module proto in a
    serialized XSpace (XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata.name 2, stats 5; XStat.metadata_id 1,
    bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1 or _first(plane, 2) != METADATA_PLANE.encode():
            continue
        stat_names = (dict(_fields(m)) for m in _map_values(plane, 5))
        proto_stats = {d.get(1) for d in stat_names
                       if d.get(2) == HLO_PROTO_STAT.encode()}
        for meta in _map_values(plane, 4):
            fields = list(_fields(meta))
            module = dict(fields).get(2, b"").decode()
            for g, stat in fields:
                st = dict(_fields(stat)) if g == 5 else {}
                if st.get(1) in proto_stats and 6 in st:
                    out[module] = _instruction_op_names(st[6])
    return out


def _instruction_op_names(hlo_proto: bytes) -> Dict[str, str]:
    names = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                d = dict(_fields(inst))
                op_name = dict(_fields(d.get(7, b""))).get(2, b"")
                names[d[1].decode()] = op_name.decode()
    return names


def read_program(path: str) -> dict:
    """The ``program`` and ``scopes`` keys of one trace file."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        op_names = hlo_op_names(f.read())
    pd = ProfileData.from_file(path)
    out = {"program": [], "scopes": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [m[0] for m in mods]
            paths = []
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                names = (op_names.get(mods[i][2], {})
                         if i >= 0 and e.start_ns < mods[i][1] else {})
                paths.append(names.get(
                    e.name.partition(" = ")[0].lstrip("%"), ""))
            out["scopes"][plane.name] = paths
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        out["program"].append([e.name.partition("#")[0],
                                               e.start_ns, e.duration_ns])
    return out


# ------------------------------------------------------------ reduction
def scope_class(path: str) -> str:
    """The innermost model scope on an op-name path, or ``NO_SCOPE``."""
    for part in reversed(path.split("/")):
        if part in SCOPE_CLASSES:
            return part
    return NO_SCOPE


def _innermost(spans) -> List[list]:
    """Nested spans flattened to [start, end, name] segments that do not
    overlap: at each instant, the innermost span that holds it."""
    out, stack, t = [], [], 0.0
    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            n, end = stack.pop()
            out.append([t, end, n])
            t = end
        if stack:
            out.append([t, start, stack[-1][0]])
        stack.append((name, start + dur))
        t = start
    while stack:
        n, end = stack.pop()
        out.append([t, end, n])
        t = end
    return [s for s in out if s[1] > s[0]]


class ProgramTrace(Trace):
    """``Trace`` over an extract that may also hold the program's spans
    and the operations' scopes; each reading of them is None where the
    extract has none."""

    def __init__(self, extract: dict):
        super().__init__(extract)
        self.program = sorted(extract.get("program", ()),
                              key=lambda e: e[1])
        self._segments = _innermost(self.program)
        self._seg_starts = [s[0] for s in self._segments]
        scopes = extract.get("scopes", {})
        self._paths = {c: {(n, s): p for (n, s, _), p in
                           zip(extract["device"][c], scopes[c])}
                       for c in self.chips if c in scopes}

    def program_span_at(self, t: float) -> Optional[str]:
        """The innermost program span that holds host time ``t``."""
        i = bisect.bisect_right(self._seg_starts, t) - 1
        if i >= 0 and t < self._segments[i][1]:
            return self._segments[i][2]
        return None

    def _gaps(self, chip: str):
        t = self.t_open
        for a, b in self.busy_intervals(chip) + [[self.t_close] * 2]:
            if a > t:
                yield t, a
            t = max(t, b)

    def idle_in(self, span_names: Iterable[str]) -> Optional[float]:
        """Device idle seconds in the window, averaged over the chips,
        while the innermost program span at the gap's middle is one of
        ``span_names``."""
        if not self._segments:
            return None
        by = self.idle_by_span()
        return sum(by.get(n, 0.0) for n in span_names)

    def idle_by_span(self) -> Dict[str, float]:
        """Device idle seconds in the window by the innermost program
        span at each gap's middle ("none": outside every span)."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            for a, b in self._gaps(c):
                k = self.program_span_at((a + b) / 2) or "none"
                tot[k] = tot.get(k, 0.0) + (b - a) * 1e-9 / len(self.chips)
        return tot

    def idle_split_by_span(self) -> Dict[str, float]:
        """Device idle seconds in the window by the innermost program
        span, each gap split by how much of it each span covers (outside
        every span: "none"); ``idle_in`` gives each gap whole to the span
        at its middle."""
        tot: Dict[str, float] = {}
        n = max(len(self.chips), 1)
        for c in self.chips:
            for a, b in self._gaps(c):
                i = max(bisect.bisect_right(self._seg_starts, a) - 1, 0)
                rest = b - a
                while i < len(self._segments) and self._segments[i][0] < b:
                    s, e, name = self._segments[i]
                    part = min(b, e) - max(a, s)
                    if part > 0:
                        tot[name] = tot.get(name, 0.0) + part * 1e-9 / n
                        rest -= part
                    i += 1
                tot["none"] = tot.get("none", 0.0) + rest * 1e-9 / n
        return tot

    def scope_ops(self, program: str) -> Optional[List[list]]:
        """[scope class, instruction, has a path, self seconds] of the
        window's operations of ``program``, summed by instruction and
        averaged over the chips."""
        if not self._paths:
            return None
        tot: Dict[tuple, float] = {}
        for c in self.chips:
            paths = self._paths.get(c, {})
            for e in self._in_window(self.ops[c]):
                if self.program_of(e) != program:
                    continue
                path = paths.get((e[0], e[1]), "")
                key = (scope_class(path), e[0].partition(" = ")[0]
                       .lstrip("%"), bool(path))
                tot[key] = tot.get(key, 0.0) + e[3] * 1e-9 / len(self.chips)
        return sorted(([*k, v] for k, v in tot.items()), key=lambda r: -r[3])

    def self_by_scope(self, program: str) -> Optional[Dict[str, float]]:
        """Device self seconds of ``program``'s window operations by
        scope class (``SCOPE_CLASSES``, else ``NO_SCOPE``)."""
        ops = self.scope_ops(program)
        if ops is None:
            return None
        tot: Dict[str, float] = {}
        for cls, _, _, s in ops:
            tot[cls] = tot.get(cls, 0.0) + s
        return tot

    def steps_in_window(self, span: str = "bench.decode") -> int:
        return sum(1 for n, s, _ in self._spans
                   if n == span and self.t_open <= s < self.t_close)


def _idle_pct(names):
    def read(t: ProgramTrace) -> Optional[float]:
        idle = t.idle_in(names)
        return None if idle is None else 100.0 * idle / t.window_s
    return read


def _decode_ms_per_step(cls):
    def read(t: ProgramTrace) -> Optional[float]:
        by = t.self_by_scope("decode")
        steps = t.steps_in_window()
        if by is None or not steps:
            return None
        return 1e3 * by.get(cls, 0.0) / steps
    return read


# each reads a ProgramTrace of one traced run
READINGS = {
    "decode.dispatch_idle_pct": _idle_pct(DISPATCH),
    "decode.post_step_idle_pct": _idle_pct(POST_STEP),
    "decode.attention_ms_per_step": _decode_ms_per_step("attention"),
    "decode.layer_scan_ms_per_step": _decode_ms_per_step(NO_SCOPE),
}


def report(t: ProgramTrace, top: int = 3) -> dict:
    """The readings, with what checks them: idle by program span beside
    the harness's idle gaps, each scope class's largest operations and
    the share of the layer scan's time that carries no path, and the
    sub-spans of the longest admission and decode step."""
    out = {"readings": {k: f(t) for k, f in READINGS.items()},
           "idle_gaps": t.idle_gaps(),
           "idle_by_span": t.idle_by_span(),
           "idle_split_by_span": t.idle_split_by_span()}
    ops = t.scope_ops("decode")
    if ops is not None:
        by = t.self_by_scope("decode")
        out["decode_scopes_s"] = by
        out["decode_top_ops"] = {
            cls: [[r[1], r[3]] for r in ops if r[0] == cls][:top]
            for cls in (*SCOPE_CLASSES, NO_SCOPE)}
        no_path = sum(r[3] for r in ops if r[0] == NO_SCOPE and not r[2])
        out["layer_scan_no_path_share"] = (
            no_path / by[NO_SCOPE] if by.get(NO_SCOPE) else None)
    out["longest"] = {}
    for span in ("engine.admit", "engine.decode"):
        whole = [e for e in t.program if e[0] == span
                 and t.t_open <= e[1] < t.t_close]
        if whole:
            _, a, d = max(whole, key=lambda e: e[2])
            out["longest"][span] = [[n, dd * 1e-9] for n, s, dd in
                                    t.program if a <= s < a + d]
    return out


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness, spec
    from bench import trace as trace_mod

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    # the harness reads the trace through ``read_xplane`` and deletes the
    # file; the program's keys are read from it on the way
    held = {}
    read = trace_mod.read_xplane

    def read_both(path):
        extract = read(path)
        held.update(extract, **read_program(path))
        return extract

    trace_mod.read_xplane = read_both
    cell = spec.Cell(spec.load_benchmark(), args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    t = ProgramTrace(held)
    print(json.dumps(result), flush=True)
    print(json.dumps({"program_trace": report(t)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
