"""The program's spans and scopes in a trace: device idle by the
innermost engine span, decode device time by model scope, the four
readings on a hand-made extract, the HLO op names read from the trace
file's wire format, and an extract without the two keys reading as
before."""
import gzip
import json

import pytest

from bench import spec
from bench.program_trace import (READINGS, ProgramTrace, _innermost,
                                 hlo_op_names, scope_class)
from bench.trace import Trace

QD = ("%_pallas_quant_dot.5 = bf16[32,3072]{1,0} custom-call(bf16[32,8192]"
      "{1,0} %f, f8e4m3fn[8192,3072]{1,0} %w)")
SCAN = "jit(wrapped)/layers/while"
ATTN = "jit(wrapped)/layers/while/body/closed_call/attention/dot_general"
MLP = ("jit(wrapped)/layers/while/body/closed_call/mlp/"
       "jit(_pallas_quant_dot)/pallas_call")
# ns; a window [0, 1300] with two decode steps. Idle gaps: [120, 215)
# in step 1's dispatch, [395, 430) in its readback, [440, 600) between
# the steps, [615, 665) in step 2's dispatch, [870, 960) in its
# bookkeeping, [970, 1300) after it
SPANS = {
    "host": [["bench.window_open", 0, 0], ["bench.decode", 100, 400],
             ["bench.decode", 600, 400], ["bench.window_close", 1300, 0]],
    "program": [
        ["engine.decode", 110, 380], ["engine.decode.dispatch", 110, 90],
        ["engine.decode.readback", 200, 220],
        ["engine.decode.bookkeep", 420, 70],
        ["engine.decode", 610, 380], ["engine.decode.dispatch", 610, 40],
        ["engine.decode.readback", 650, 250],
        ["engine.decode.bookkeep", 900, 90]],
    "device": {"/device:TPU:0": [
        ["%fusion.9 = bf16[8]{0} fusion()", 0, 120],
        ["%while.3 = (s32[]) while(s32[] %t)", 210, 190],
        ["%fusion.1 = bf16[8]{0} fusion()", 215, 85], [QD, 300, 80],
        ["%copy.5 = bf16[8]{0} copy()", 380, 15],
        ["%fusion.7 = bf16[8]{0} fusion()", 430, 10],
        ["%fusion.8 = bf16[8]{0} fusion()", 600, 15],
        ["%while.3 = (s32[]) while(s32[] %t)", 660, 220],
        ["%fusion.1 = bf16[8]{0} fusion()", 665, 95], [QD, 760, 90],
        ["%copy.5 = bf16[8]{0} copy()", 850, 20],
        ["%fusion.7 = bf16[8]{0} fusion()", 960, 10]]},
    "scopes": {"/device:TPU:0": [
        "jit(wrapped)/logits/dot_general", SCAN, ATTN, MLP, "",
        "jit(wrapped)/logits/dot_general", "jit(wrapped)/embed/take", SCAN,
        ATTN, MLP, "", "jit(wrapped)/logits/dot_general"]},
}


def _recorded():
    path = spec.BENCH / "tests" / "data" / "sc2_code_trace.json.gz"
    with gzip.open(path) as f:
        return json.load(f)


def test_innermost_span_holds_each_instant():
    segs = _innermost([["p", 0, 100], ["a", 10, 10], ["b", 30, 10],
                       ["q", 200, 5]])
    assert segs == [[0, 10, "p"], [10, 20, "a"], [20, 30, "p"],
                    [30, 40, "b"], [40, 100, "p"], [200, 205, "q"]]
    t = ProgramTrace(SPANS)
    assert [t.program_span_at(x) for x in (105, 150, 300, 495, 950)] == \
        [None, "engine.decode.dispatch", "engine.decode.readback", None,
         "engine.decode.bookkeep"]


def test_idle_by_the_innermost_engine_span():
    t = ProgramTrace(SPANS)
    assert t.idle_in(["engine.decode.dispatch"]) == pytest.approx(145e-9)
    assert t.idle_in(["engine.decode.readback",
                      "engine.decode.bookkeep"]) == pytest.approx(125e-9)
    by = t.idle_by_span()
    assert by["none"] == pytest.approx(490e-9)
    assert sum(by.values()) == pytest.approx(t.window_s - t.busy_s())
    # each gap split across the spans it crosses
    assert t.idle_split_by_span() == pytest.approx({
        "engine.decode.dispatch": 115e-9, "engine.decode.readback": 85e-9,
        "engine.decode.bookkeep": 140e-9, "none": 420e-9})


def test_decode_self_time_by_scope():
    t = ProgramTrace(SPANS)
    assert scope_class(MLP) == "mlp" and scope_class("") == "layer scan"
    by = t.self_by_scope("decode")
    # the loops' own time (10 + 15) and XLA's copies with no path (15 + 20)
    assert by == pytest.approx({"attention": 180e-9, "mlp": 170e-9,
                                "layer scan": 60e-9, "logits": 20e-9,
                                "embed": 15e-9})
    assert t.self_by_scope("prefill") == {}
    no_path = [r for r in t.scope_ops("decode") if not r[2]]
    assert [r[:2] for r in no_path] == [["layer scan", "copy.5"]]


def test_the_four_readings_by_hand():
    got = {k: f(ProgramTrace(SPANS)) for k, f in READINGS.items()}
    assert got == pytest.approx({
        "decode.dispatch_idle_pct": 100 * 145 / 1300,
        "decode.post_step_idle_pct": 100 * 125 / 1300,
        "decode.attention_ms_per_step": 1e-6 * 180 / 2,
        "decode.layer_scan_ms_per_step": 1e-6 * 60 / 2})


@pytest.mark.parametrize("extract", ["hand-made", "recorded"])
def test_an_extract_without_the_program_reads_as_before(extract):
    ex = _recorded() if extract == "recorded" else {
        k: v for k, v in SPANS.items() if k not in ("program", "scopes")}
    old, new = Trace(ex), ProgramTrace(ex)
    assert new.busy_s() == old.busy_s() and new.window_s == old.window_s
    assert new.idle_gaps() == old.idle_gaps()
    assert new.device_ops() == old.device_ops()
    assert all(f(new) is None for f in READINGS.values())


def _msg(*fields) -> bytes:
    """A protobuf message of (field number, str / bytes / int / message)
    fields, in the wire format."""
    def varint(v):
        out = b""
        while True:
            out += bytes([v & 0x7F | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_hlo_op_names_from_the_metadata_plane():
    inst = lambda name, op: _msg((1, name), (2, "fusion"),
                                 (7, _msg((1, "dot"), (2, op))))
    module = _msg((1, "jit_f"), (3, _msg(
        (1, "main"), (2, inst("fusion.1", ATTN)), (2, _msg((1, "copy.5"))))))
    stat = _msg((1, 7), (6, _msg((1, module))))
    meta = _msg((1, 3), (2, "jit_f(5)"), (5, _msg((1, 8), (3, 12))),
                (5, stat))
    plane = _msg((1, 2), (2, "/host:metadata"),
                 (4, _msg((1, 3), (2, meta))),
                 (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
                 (5, _msg((1, 8), (2, _msg((1, 8), (2, "other"))))))
    other = _msg((2, "/device:TPU:0"), (4, _msg((1, 3), (2, meta))))
    assert hlo_op_names(_msg((1, other), (1, plane))) == {
        "jit_f(5)": {"fusion.1": ATTN, "copy.5": ""}}
