"""The trace reductions on a small hand-made trace and on a recorded one."""
import json
import os

import pytest

from chipbench import trace as T

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, name, start, dur):
    return {"plane": plane, "line": "XLA Ops", "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


HAND = [
    ev(D0, "fusion.1", 0, 100), ev(D0, "fusion.2", 50, 100),   # overlap
    ev(D0, "all-reduce.3", 200, 100),                           # exposed 60
    ev(D0, "fusion.4", 260, 100),
    ev(D0, "_fwd_kernel", 400, 50), ev(D0, "_fwd_kernel", 500, 50),
    ev(D1, "fusion.1", 0, 300),
    {"plane": "/host:CPU", "line": "python", "name": "cb:decode",
     "start_ns": 140.0, "dur_ns": 70.0},
]


def test_busy_is_the_union_of_intervals():
    assert T.busy_ns(HAND, D0) == 150 + 160 + 50 + 50
    assert T.busy_s_mean(HAND) == pytest.approx((410 + 300) / 2 / 1e9)
    assert T.idle_percent(HAND, 1e-6) == pytest.approx(
        100 * (1 - (410 + 300) / 2 / 1000))


def test_kernel_time_by_name():
    assert T.kernel_ns(HAND, r"_fwd_kernel") == (100.0, 2)
    assert T.kernel_ns(HAND, r"^fusion\.1$") == (400.0, 2)


def test_collective_time_not_overlapped_by_compute():
    assert T.exposed_collective_ns(HAND, D0) == 60.0
    assert T.exposed_collective_ns(HAND, D1) == 0.0


def test_breakdown_names_gaps_by_host_span():
    b = T.breakdown(HAND)
    assert b["device_ops"][0] == ["fusion.1", 400 / 1e9]
    assert b["idle_gaps"][0] == ["cb:decode", 50 / 1e9]


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_reduces():
    with open(RECORDED) as f:
        rec = json.load(f)
    events = rec["events"]
    assert T.device_planes(events) == [D0]
    assert T.busy_s_mean(events) == pytest.approx(rec["busy_s"], rel=1e-9)
    for pat, (ns, n) in rec["kernels"].items():
        assert T.kernel_ns(events, pat) == (pytest.approx(ns), n)


def test_flash_roofline_reads_forward_and_backward_calls():
    import types
    from chipbench import flops
    from chipbench import run as R
    with open(RECORDED) as f:
        events = json.load(f)["events"]
    read = R.load_module(os.path.join(R.BENCH, "metrics",
                                      "flash_roofline.train.py")).read
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    attn = {"b": 12, "s": 4096, "h": 15, "kh": 5, "hd": 64, "itemsize": 2}
    ctx = types.SimpleNamespace(trace_events=events, peak=peak)
    fl = [e for e in events if e["name"].startswith("%flash_attention")]
    fwd = [e for e in fl if len(T.outputs(e["name"])) == 2]
    bwd = [e for e in fl if len(T.outputs(e["name"])) == 3]
    assert len(fwd) == 2 and len(bwd) == 2
    shape = (12, 15, 5, 4096, 64, 2)
    least = (2 * flops.least_time(*flops.flash_fwd(*shape), 197e12, 819e9)
             + 2 * flops.least_time(*flops.flash_bwd(*shape), 197e12, 819e9))
    want = 100 * least / (sum(e["dur_ns"] for e in fl) / 1e9)
    got = read({"attn": attn}, ctx)
    assert got == pytest.approx(want)
    assert 0 < got < 100
