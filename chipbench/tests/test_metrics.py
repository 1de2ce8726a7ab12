"""Metric arithmetic on hand-made records, and the harness's refusals."""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import run as R
from chipbench.reference.dense import Dims


def metric(name):
    return R.load_module(os.path.join(R.BENCH, "metrics", name + ".py")).read


def ctx(window="arrivals", chips=1):
    return types.SimpleNamespace(
        traffic={"window": window}, chips=chips,
        peak={"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0},
        trace_events=None, trace_window_s=0.0)


def test_ttft_p95_is_over_all_requests_not_a_median_of_chunks():
    # 38 fast requests then two slow ones, in 4 chunks of 10: the median
    # of the chunks' p95 hides the slow ones, the p95 of all 40 does not
    lat = [0.010] * 38 + [1.0] * 2
    reqs = [{"due": 0.0, "times": [x], "start": 0.0} for x in lat]
    got = metric("ttft_p95_ms")({"requests": reqs, "t_end": 2.0}, ctx())
    assert got == pytest.approx(1e3 * np.percentile(lat, 95))
    chunks = [np.percentile(lat[i:i + 10], 95) for i in range(0, 40, 10)]
    assert got > 1e3 * float(np.median(chunks)) * 5


def test_ttft_counts_a_request_with_no_first_token_until_the_run_ended():
    # three of four never got a token: they wait from their due time to
    # the end of the run, 9.5 s, and set the tail
    reqs = [{"due": 0.5, "times": [], "start": None}] * 3 + [
        {"due": 0.0, "times": [0.1], "start": 0.0}]
    got = metric("ttft_p95_ms")({"requests": reqs, "t_end": 10.0}, ctx())
    assert got == pytest.approx(9500.0)


def test_itl_and_tokens_count_only_the_window_under_a_backlog():
    reqs = [{"times": [0.5, 1.5, 2.0, 2.2, 3.5]},
            {"times": [1.2, 1.3, 1.9]}]
    rec = {"requests": reqs, "window": (1.0, 3.0)}
    # stamps in (1, 3]: 1.5, 2.0, 2.2 and 1.2, 1.3, 1.9
    assert metric("serve_tok_s")(rec, ctx("backlog")) == pytest.approx(3.0)
    gaps = [0.5, 0.2, 0.1, 0.6]
    assert metric("itl_p95_ms")(rec, ctx("backlog")) == pytest.approx(
        1e3 * np.percentile(gaps, 95))
    every = [1.0, 0.5, 0.2, 1.3, 0.1, 0.6]
    assert metric("itl_p95_ms")(rec, ctx("arrivals")) == pytest.approx(
        1e3 * np.percentile(every, 95))


def test_engine_host_ms_leaves_out_backend_calls_and_waits():
    rec = {"window": (0.0, 10.0),
           "prefills": [(1.0, 2.0, 8)],
           "decodes": [(2.5, 3.0, [8]), (3.2, 3.7, [9])],
           "sleeps": [(0.2, 1.0)]}
    # 3.7 s of wall, 2.0 in calls, 0.8 asleep, two decode iterations
    assert metric("engine_host_ms")(rec, ctx()) == pytest.approx(450.0)


def test_train_rates_are_over_the_whole_window():
    rec = {"tokens": 1000, "window_s": 4.0, "flops_per_token": 0.2,
           "input_waits": [0.001, 0.003]}
    assert metric("train_tok_s")(rec, ctx()) == 250.0
    assert metric("train_mfu")(rec, ctx(chips=1)) == pytest.approx(50.0)
    assert metric("input_wait_ms.train")(rec, ctx()) == pytest.approx(2.0)


def test_decode_mfu_is_least_time_over_measured_time():
    dm = Dims(d=2, h=1, kh=1, hd=2, f=2, layers=1, vocab=4, tied=True,
              eps=1e-5, theta=1e4)
    rec = {"window": (0.0, 10.0), "dims": dm, "weight_itemsize": 2,
           "kv_itemsize": 2, "decodes": [(1.0, 2.0, [3])]}
    from chipbench import flops
    ops, nbytes = flops.decode_step(dm, [3], 2, 2)
    want = 100.0 * max(ops / 100.0, nbytes / 10.0) / 1.0
    assert metric("decode_mfu")(rec, ctx()) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(R.NoChip, match="not in chipbench/peaks.json"):
        R.devices_for(1, {"devices": {"TPU v5 lite": {}}}, require_chip=False)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(R.BENCH, "run.py"), "--workload",
         "serve.smollm-360m.chat", "--seed", str(2 ** 31 + 5), "--seconds",
         "1", "--trace", "0"], cwd=R.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
