"""The reduction from a profiler trace to busy, idle and collective time."""

import json
import os

import pytest

from bench import devtrace

# window 90..490 ns; device 0 ops overlap, one collective; device 1 idle
# for the first half
TOY = {"devices": {
    "/device:TPU:0": [["fusion.1", "fusion", 100, 50],
                      ["all-gather.2", "all-gather", 140, 30],
                      ["fusion.3", "fusion", 300, 100],
                      ["copy.4", "copy", 480, 40],
                      ["while.5", "while", 90, 400],
                      ["before", "fusion", 10, 20]],
    "/device:TPU:1": [["fusion.1", "fusion", 290, 100],
                      ["all-gather-start.2", "all-gather-start", 395, 5]]},
    "host": [["window", 90, 400], ["call", 95, 190], ["fetch", 290, 150],
             ["setup", 0, 80]]}


def test_toy_trace_one_chip():
    r = devtrace.reduce(TOY, chips=1)
    assert r.window_s == pytest.approx(400e-9)
    # union on device 0: [100,170] + [300,400] + [480,490] = 180 ns
    assert r.busy_s == pytest.approx(180e-9)
    assert r.collective_s == pytest.approx(30e-9)
    # gaps: [90,100] in call, [170,300] mid 235 in call, [400,480] mid 440
    # at the end of fetch, [480..490] busy
    assert [(round(s * 1e9), n) for s, n in r.gaps] == [
        (130, "call"), (80, "fetch"), (10, "call")]
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(100e-9)]
    assert len(b["idle_gaps"]) == 3


def test_toy_trace_two_chips_means():
    r = devtrace.reduce(TOY, chips=2)
    # device 1 busy [290,390] + [395,400] = 105 ns; mean (180 + 105) / 2
    assert r.busy_s == pytest.approx(142.5e-9)
    assert r.collective_s == pytest.approx((30 + 5) / 2 * 1e-9)
    assert r.op_s["fusion.1"] == pytest.approx((50 + 100) / 2 * 1e-9)


def test_no_collective_reads_nothing():
    data = {"devices": {"/device:TPU:0": [["fusion", "fusion", 0, 10]]},
            "host": [["window", 0, 20]]}
    r = devtrace.reduce(data, chips=1)
    assert r.collective_s is None and r.busy_s == pytest.approx(10e-9)


def test_missing_window_or_chip_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {}, "host": []}, chips=1)
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {}, "host": [["window", 0, 5]]}, chips=1)


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_q19_sugar.json")


def _sweep_busy(ops, ws, we):
    """Busy time by a sweep over op start/end events (an independent
    algorithm from the reduction's interval union)."""
    ev = []
    for _, op, s, d in ops:
        if op in devtrace.CONTAINERS:
            continue
        a, b = max(s, ws), min(s + d, we)
        if b > a:
            ev += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, k in sorted(ev):
        if depth > 0:
            busy += t - last
        depth += k
        last = t
    return busy


def test_recorded_chip_trace():
    with open(FIXTURE) as f:
        data = json.load(f)
    r = devtrace.reduce(data, chips=1)
    (_, ws, wd), = [h for h in data["host"] if h[0] == "window"]
    ops = data["devices"]["/device:TPU:0"]
    assert r.window_s == pytest.approx(0.01331665, abs=1e-12)
    assert r.busy_s == pytest.approx(0.005344065, abs=1e-12)
    assert r.busy_s == pytest.approx(_sweep_busy(ops, ws, ws + wd) / 1e9,
                                     abs=1e-12)
    assert sum(s for s, _ in r.gaps) == pytest.approx(r.window_s - r.busy_s)
    assert r.collective_s is None
    # the scan's while op only contains the others
    assert any(op == "while" for _, op, _, _ in ops)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.115 s32[65536]"
    assert b["idle_gaps"][0][0] == "fetch"
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10


@pytest.mark.parametrize("hlo,label", [
    ("%fusion.115 = s32[65536]{0:T(1024)S(1)} fusion(s32[15000000]{0:T(1024)}"
     " %get-tuple-element.540), kind=kCustom, calls=%fused_computation.6",
     ("fusion.115 s32[65536]", "fusion")),
    ("%while.7 = (s32[]{:T(128)}, pred[18,139255]{1,0:T(8,128)(4,1)}) "
     "while((s32[]{:T(128)}) %tuple.75), condition=%c, body=%b",
     ("while.7 tuple", "while")),
    ("%all-gather.3 = s32[4,1235]{1,0} all-gather(s32[1,1235]{1,0} %x), "
     "dimensions={0}", ("all-gather.3 s32[4,1235]", "all-gather")),
])
def test_op_label(hlo, label):
    assert devtrace.op_label(hlo) == label
