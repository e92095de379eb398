"""The yardstick's arithmetic: operation counts against hand counts, and
the trace reduction against hand-checked events."""
import json

import pytest

from chipbench_tiny import BENCH, spec

from chipbench import opcount, tracing


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_ecg_window_ops_hand_count():
    # conv 32 positions x 128 x 8, fc1 256 x 123, fc2 123 x 10 MACs
    macs = 32 * 128 * 8 + 256 * 123 + 123 * 10
    assert macs == 65486
    assert opcount.ecg_window_ops(_config("ecg-bss2")) == 2 * macs == 130972


def test_lm_token_macs_hand_count():
    d, ff, v = 2560, 6912, 50304
    layer = d * 3 * d + d * d + 3 * d * ff     # qkv, o, up + gate + down
    assert opcount.lm_token_macs(_config("stablelm-3b-4l")) == \
        4 * layer + d * v == 445_972_480


def test_mvm_work_and_least_time():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    w = opcount.Work()
    # 2 rows, K=128, N=4: 2*2*128*4 ops; bytes: codes 512, codes in 256,
    # tables 4*(2*4 + 1*4) + 4*(128+4), outputs 2*4*4
    nbytes = opcount.mvm_bytes(2, 128, 4)
    assert nbytes == 512 + 256 + 48 + 528 + 32
    w.add(opcount.mvm_ops(2, 128, 4), nbytes, peak, n=3)
    assert w.ops == 3 * 2048 and w.calls == 3
    assert w.min_s == pytest.approx(3 * max(2048 / 100.0, nbytes / 10.0))


def test_lm_step_counts_head_at_last_position_only():
    cfg = _config("stablelm-3b-4l")
    peak = spec.peaks()["chips"]["TPU v5 lite"]
    mvm, total = opcount.Work(), opcount.Work()
    opcount.lm_step(cfg, 4, 512, 0, peak, mvm, total)
    layers = opcount.lm_token_macs(cfg) - 2560 * 50304
    attn = 4 * 4 * 2560 * (512 * 513 // 2) * 4  # 4 rows, causal keys, 4 L
    assert total.ops == 2 * (4 * 512 * layers + 4 * 2560 * 50304) + attn
    assert mvm.calls == 5 * 4 + 1


def test_peaks_known_kind_and_unknown_kind_raises():
    assert spec.peak("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        spec.peak("TPU v9 imaginary")


def test_union_and_clip():
    assert tracing.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [[0, 3],
                                                               [5, 9]]
    assert tracing.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_hand_checked():
    """Window [100, 200] ns on the host; two chips; ops overlap and spill
    over the window's edges; gaps are named by the innermost host span."""
    devices = {
        "/device:TPU:0": [("%fusion.1", 90, 120),
                          ("%analog_mvm_split_pallas.3", 110, 150),
                          ("%copy", 170, 180)],
        "/device:TPU:1": [("%fusion.1", 100, 140),
                          ("%analog_mvm_split_pallas.3", 190, 230)],
    }
    host = [("bench.window", 100, 200), ("bench.call", 100, 200),
            ("ecg.copy_in", 150, 165)]
    s = tracing.reduce(devices, host, window_s=0.0)
    assert s.window_s == pytest.approx(100e-9)
    # chip 0 busy [100,150] + [170,180] = 60; chip 1 [100,140] + [190,200]
    assert s.busy_s == pytest.approx((60 + 50) / 2 * 1e-9)
    assert s.ops == 5 // 2
    assert s.op_time_s["%analog_mvm_split_pallas.3"] == pytest.approx(
        (40 + 10) * 1e-9)
    assert s.kernel_s(tracing.ANALOG_MVM) == pytest.approx(50e-9)
    # gaps: chip 0 (150,170) mid 160 in ecg.copy_in, (180,200) in
    # bench.call; chip 1 (140,190) mid 165 on the edge of ecg.copy_in
    assert s.gaps[0] == (pytest.approx(50e-9), "ecg.copy_in")
    assert [g[1] for g in s.gaps] == ["ecg.copy_in", "ecg.copy_in",
                                      "bench.call"]
    b = s.breakdown()
    # fusion.1: 20 ns on chip 0 inside the window, 40 on chip 1
    assert b["device_ops"][0] == ["%fusion.1", pytest.approx(60e-9)]
    assert len(b["idle_gaps"]) == 3


FIXTURE = BENCH / "tests" / "data" / "ecg_stream.xplane.pb"


def test_recorded_chip_trace():
    """Two ecg-stream requests traced on a TPU v5e (83 KB): the window is
    the harness's ``bench.window`` span, 4,424,930 ns; each request runs
    28 device operations (pre-processing and the chain, 8.5 us); busy is
    the union of the operations' intervals, checked here against a 1-ns
    timeline; the longest gaps fall where the host copies the next raw
    window in."""
    import numpy as np

    devices, host = tracing.read_events(str(FIXTURE))
    assert list(devices) == ["/device:TPU:0"]
    lo, hi = next((s, e) for n, s, e in host if n == "bench.window")
    assert hi - lo == 4_424_930
    timeline = np.zeros(int(hi - lo), bool)
    for _, s, e in devices["/device:TPU:0"]:
        timeline[int(max(s, lo) - lo):int(min(e, hi) - lo)] = True
    s = tracing.summarize(FIXTURE.parent, 0.0)
    assert s.window_s == pytest.approx(4_424_930e-9)
    assert s.busy_s == pytest.approx(timeline.sum() * 1e-9) == \
        pytest.approx(16_993e-9)
    assert s.ops == 56 == 2 * 28
    assert s.kernel_s(tracing.MAXMIN_POOL) == pytest.approx(1_076e-9)
    assert s.kernel_s(tracing.MEGAKERNEL) == pytest.approx(1_181e-9)
    assert s.kernel_s(tracing.ANALOG_MVM) == 0.0
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["ecg.copy_in", pytest.approx(0.001903126)]
    assert sum(t for _, t in b["idle_gaps"]) <= s.window_s - s.busy_s


def test_control_flow_is_left_out():
    devices = {"/device:TPU:0": [("%while.3", 0, 100), ("%fusion.1", 10, 20),
                                 ("%fusion.2", 50, 60)]}
    devices["/device:TPU:0"] = [
        (n, s, e) for n, s, e in devices["/device:TPU:0"]
        if not tracing.CONTROL_FLOW.match(n)]
    s = tracing.reduce(devices, [("bench.window", 0, 100)], 0.0)
    assert s.busy_s == pytest.approx(20e-9) and s.ops == 2
    assert tracing.CONTROL_FLOW.match("%while.4 = (s32[]) while(")
    assert not tracing.CONTROL_FLOW.match("%while_body_fusion.2")
