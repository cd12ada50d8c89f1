"""The trace reduction: busy union over the innermost operations, idle
share, self time of nested loop events, kernel time by name and by the
program whose harness span holds it, idle gaps by what the host did, and
the call shapes read from an instruction's text."""

import pytest

from bench.trace import Trace, call_shapes, shape_bytes

TRANSFORM = ("%_pallas_transform.1 = bf16[6144,8192]{1,0:T(8,128)(2,1)} "
             "custom-call(bf16[6144,8192]{1,0:T(8,128)(2,1)} %bitcast.7, "
             "bf16[2,128,128]{2,1,0:T(8,128)(2,1)} %constant.9), "
             "custom_call_target=\"tpu_custom_call\"")
QUANT_DOT = ("%_pallas_quant_dot.5 = bf16[32,3072]{1,0:T(8,128)(2,1)} "
             "custom-call(bf16[32,8192]{1,0} %f, bf16[2,128,128]{2,1,0} %m, "
             "f8e4m3fn[8192,3072]{1,0} %w, f32[1,3072]{1,0} %s)")
# ns; a window [0, 1300] with one admission, two decode steps and a wait
SMALL = {
    "host": [["bench.window_open", 0, 0], ["bench.admit", 100, 300],
             ["bench.decode", 500, 200], ["bench.wait", 800, 200],
             ["bench.decode", 1000, 200], ["bench.window_close", 1300, 0]],
    "device": {"/device:TPU:0": [
        ["%while.3 = (s32[]) while(s32[] %t)", 120, 260],
        [TRANSFORM, 150, 100], ["%fusion.1 = bf16[8]{0} fusion()", 260, 110],
        ["%while.16 = (s32[]) while(s32[] %t)", 520, 170],
        [QUANT_DOT, 530, 70], ["%fusion.2 = bf16[8]{0} fusion()", 610, 70],
        ["%while.16 = (s32[]) while(s32[] %t)", 1010, 180],
        [QUANT_DOT, 1020, 160],
        ["%copy.9 = bf16[8]{0} copy()", 1400, 50]]},   # after the window
}


def test_busy_is_the_union_of_innermost_operations():
    t = Trace(SMALL)
    assert t.window_s == pytest.approx(1300e-9)
    assert t.busy_s() == pytest.approx((100 + 110 + 70 + 70 + 160) * 1e-9)


def test_kernel_time_by_name_and_program():
    t = Trace(SMALL)
    assert [e[1] for e in t.kernel_events(r"_pallas_transform\b",
                                          "prefill")] == [150]
    assert t.kernel_events(r"_pallas_transform\b", "decode") == []
    assert [e[2] for e in t.kernel_events(r"_pallas_quant_dot\b",
                                          "decode")] == [70, 160]
    assert t.kernel_events(r"_pallas_quant_dot\b", "prefill") == []


def test_device_ops_count_self_time():
    ops = dict(Trace(SMALL).device_ops())
    assert ops["prefill:while.3"] == pytest.approx(50e-9)
    assert ops["decode:while.16"] == pytest.approx((30 + 20) * 1e-9)
    assert ops["decode:_pallas_quant_dot.5"] == pytest.approx(230e-9)
    assert "other:copy.9" not in ops


def test_idle_gaps_by_what_the_host_was_doing():
    gaps = dict(Trace(SMALL).idle_gaps())
    assert gaps["waiting for arrivals"] == pytest.approx(340e-9)
    assert gaps["admission (prefill + insert)"] == pytest.approx(10e-9)
    assert gaps["decode step (dispatch + host sync)"] == pytest.approx(10e-9)
    assert gaps["harness between steps"] == pytest.approx(430e-9)
    assert sum(gaps.values()) == pytest.approx(1300e-9 - Trace(SMALL).busy_s())


def test_call_shapes_read_past_layouts_and_tuples():
    res, ops = call_shapes(QUANT_DOT)
    assert res == [("bf16", (32, 3072))]
    assert [o[1] for o in ops] == [(32, 8192), (2, 128, 128), (8192, 3072),
                                   (1, 3072)]
    assert shape_bytes(ops[2:]) == 8192 * 3072 + 4 * 3072
    res, ops = call_shapes("%while.3 = (s32[]{:T(128)}, bf16[1,8]{1,0}) "
                           "while((s32[]{:T(128)}, bf16[1,8]{1,0}) %t)")
    assert res == [("s32", ()), ("bf16", (1, 8))] == ops


def _recorded():
    """A slice of a traced ``sc2_code`` run on one TPU v5 lite: one
    admission and two decode steps, the text after ' = ' kept for the
    Pallas calls only."""
    import gzip
    import json

    from bench import spec

    path = spec.BENCH / "tests" / "data" / "sc2_code_trace.json.gz"
    with gzip.open(path) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_its_own_parts():
    t = Trace(_recorded())
    busy = t.busy_s()
    assert 0 < busy < t.window_s
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(t.window_s - busy)
    assert set(gaps) <= {"admission (prefill + insert)",
                         "decode step (dispatch + host sync)",
                         "harness between steps"}
    ops = t.device_ops()
    assert len(ops) == 10 and ops[0][0].startswith("prefill:")
    assert ops == sorted(ops, key=lambda kv: -kv[1])


def test_recorded_trace_shows_the_transform_and_no_quant_dot():
    from types import SimpleNamespace

    from bench import spec
    from bench.metrics_util import kernel_roofline_pct

    t = Trace(_recorded())
    assert t.kernel_events(r"_pallas_quant_dot\b") == []
    # one call per layer (10): the 2048-row bucket in the admission, the
    # 16 slots in each decode step, each row as 3 groups of 8192; the
    # kernel pads the admission's 6144 rows to its row block
    rows = lambda program: [call_shapes(e[0])[1][0][1] for e in
                            t.kernel_events(r"_pallas_transform\b", program)]
    assert rows("prefill") == [(6240, 8192)] * 10
    assert rows("decode") == [(3 * 16, 8192)] * 20
    peaks = spec.peaks("TPU v5 lite")

    def share(prompt_len, kernel="hadamard"):
        log = SimpleNamespace(open=0.0, admits=[(0.0, 0.15, prompt_len)],
                              decodes=[(0.15, 0.17, [9] * 16)] * 2)
        run = SimpleNamespace(trace=t, peaks=peaks, log=log, chips=1,
                              mix={"prefill_len": 2048, "slots": 16},
                              matmul_peak=lambda: peaks["flops"]["fp8_e4m3"])
        return kernel_roofline_pct(run, kernel, "prefill")

    assert 50 < share(2048) < 100
    # the transform is bound by its bytes, nearly all of them rows: a
    # prompt of half the bucket does half the useful work in the same time
    assert share(1024) == pytest.approx(share(2048) / 2, rel=1e-3)
    assert share(2048, "quant_dot") is None


def test_each_kernel_call_counts_the_tokens_of_its_own_step():
    from types import SimpleNamespace

    from bench.metrics_util import kernel_roofline_pct

    t = Trace(SMALL)
    assert [t.step_of(e) for e in t.kernel_events(r"_pallas_quant_dot\b")] \
        == [0, 1]
    assert t.step_of(["%x", 50, 1]) is None      # outside every span
    log = SimpleNamespace(open=0.0, admits=[(0.1, 0.4, 64)],
                          decodes=[(0.5, 0.7, [3] * 32), (1.0, 1.2, [3] * 8)])
    run = SimpleNamespace(trace=t, log=log, mix={"prefill_len": 2048,
                                                 "slots": 32},
                          peaks={"hbm_bytes_per_s": 1e30}, chips=1,
                          matmul_peak=lambda: 1.0)
    # compute-bound at this peak: each call's operations, at the slots
    # its own step had in use, over the two calls' time
    ops = [2 * n * 8192 * 3072 + n * 8192 * 13 for n in (32, 8)]
    assert kernel_roofline_pct(run, "quant_dot", "decode") \
        == pytest.approx(100 * sum(ops) / ((70 + 160) * 1e-9))


# two chips; chip 0's events stop after the second decode step, chip 1
# keeps all four. ns; a window [0, 2000]
CUT = {
    "host": [["bench.window_open", 0, 0], ["bench.admit", 100, 300],
             ["bench.decode", 500, 200], ["bench.decode", 800, 200],
             ["bench.decode", 1100, 200], ["bench.decode", 1400, 200],
             ["bench.window_close", 2000, 0]],
    "device": {
        "/device:TPU:0": [[TRANSFORM, 150, 100], [QUANT_DOT, 520, 100],
                          [QUANT_DOT, 820, 100]],
        "/device:TPU:1": [[TRANSFORM, 150, 100], [QUANT_DOT, 520, 100],
                          [QUANT_DOT, 820, 100], [QUANT_DOT, 1120, 100],
                          [QUANT_DOT, 1420, 100]]},
}


def test_window_ends_at_the_first_span_a_chip_kept_nothing_of():
    t = Trace(CUT)
    assert t.kept_until("/device:TPU:0") == 1100
    assert t.kept_until("/device:TPU:1") == 2000
    # every reading stops at the third decode step's start, on both chips
    assert t.window_s == pytest.approx(1100e-9)
    assert t.marked_s == pytest.approx(2000e-9)
    assert t.busy_s() == pytest.approx(300e-9)
    assert [e[1] for e in t.kernel_events(r"_pallas_quant_dot\b")] \
        == [520, 820, 520, 820]
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())
    assert dict(t.device_ops())["decode:_pallas_quant_dot.5"] \
        == pytest.approx(200e-9)


def test_idle_tail_waiting_for_arrivals_keeps_the_window():
    # the device idles after the last step while the host waits: no
    # program span goes without its operations
    tail = {"host": CUT["host"][:4] + [["bench.wait", 1000, 1000],
                                       ["bench.window_close", 2000, 0]],
            "device": {"/device:TPU:0": CUT["device"]["/device:TPU:0"]}}
    t = Trace(tail)
    assert t.window_s == t.marked_s == pytest.approx(2000e-9)
    # the last operation ends at 920
    assert dict(t.idle_gaps())["waiting for arrivals"] \
        == pytest.approx(1080e-9)
    assert Trace(SMALL).window_s == Trace(SMALL).marked_s


def test_a_plane_with_no_events_is_cut_at_the_opening():
    from types import SimpleNamespace

    from bench import spec

    empty = dict(CUT, device=dict(CUT["device"], **{"/device:TPU:2": []}))
    t = Trace(empty)
    assert t.kept_until("/device:TPU:2") == t.t_close == t.t_open
    assert t.window_s == 0 and t.busy_s() == 0
    assert t.idle_gaps() == [] and t.device_ops() == []
    assert t.kernel_events(r"_pallas_quant_dot\b") == []
    idle = spec.load_reader("device.idle_pct")
    assert idle(SimpleNamespace(trace=t)) is None


def test_recorded_trace_reads_as_before_the_cut():
    # the sc2_code slice lost no event: the numbers of the reduction
    # before the window could end early, to the last digit
    from types import SimpleNamespace

    from bench import spec
    from bench.metrics_util import kernel_roofline_pct

    t = Trace(_recorded())
    assert t.t_close == t.t_marked
    assert t.busy_s() == 0.181942397
    assert t.window_s == 0.19063380000000002
    assert t.idle_gaps() == [
        ["admission (prefill + insert)", 0.004718798999999974],
        ["decode step (dispatch + host sync)", 0.003972603999999775]]
    peaks = spec.peaks("TPU v5 lite")
    log = SimpleNamespace(open=0.0, admits=[(0.0, 0.15, 2048)],
                          decodes=[(0.15, 0.17, [9] * 16)] * 2)
    run = SimpleNamespace(trace=t, peaks=peaks, log=log, chips=1,
                          mix={"prefill_len": 2048, "slots": 16},
                          matmul_peak=lambda: peaks["flops"]["fp8_e4m3"])
    assert kernel_roofline_pct(run, "hadamard", "prefill") \
        == 67.31941048792855
