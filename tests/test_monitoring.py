"""The live observability plane (``repro.monitoring``).

Contracts under test: the registry's histograms are deterministic
(fixed bucket edges, interpolated quantiles); ``Stats``/``CkptStats``
stay field-compatible views whose committed bench metrics are
bit-identical with monitoring enabled (the one-check-per-hook pattern);
the serve engine takes mid-run snapshots whose live IO gauges actually
change within one ``run()``; and IO backpressure defers an admission
that the page/slot-only gate would have accepted.
"""
import json
import os
import sys

import numpy as np
import pytest

from repro.core import DbMode, NULL_GUID, Runtime, spawn_main
from repro.core.runtime import Stats
from repro.monitoring import DEFAULT_LATENCY_EDGES, Histogram, Registry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_SNAPDIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "snapshots")


def _snapshot(name):
    with open(os.path.join(_SNAPDIR, f"BENCH_{name}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- registry units


def test_registry_counters_gauges_and_snapshot_order():
    reg = Registry()
    reg.declare("a.count", 0)
    reg.inc("a.count")
    reg.inc("a.count", 3)
    reg.set("b.gauge", 2.5)
    assert reg.value("a.count") == 4
    assert reg.value("missing", default=-1) == -1
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap == {"a.count": 4, "b.gauge": 2.5}
    # prefix filtering
    assert reg.snapshot("a.") == {"a.count": 4}


def test_histogram_deterministic_quantiles():
    h = Histogram("lat")
    assert h.quantile(0.5) == 0.0          # empty
    for x in (0.001, 0.002, 0.004, 0.008, 0.5):
        h.observe(x)
    assert h.count == 5
    assert h.total == pytest.approx(0.515)
    # order-independence: same observations, any order, same quantiles
    h2 = Histogram("lat")
    for x in (0.5, 0.004, 0.001, 0.008, 0.002):
        h2.observe(x)
    assert h.quantile(0.5) == h2.quantile(0.5)
    assert h.quantile(0.99) == h2.quantile(0.99)
    # quantiles are bracketed by the observation range's buckets
    assert 0.0 < h.quantile(0.5) < 0.5
    assert h.quantile(0.99) <= DEFAULT_LATENCY_EDGES[-1]
    # overflow clamps to the last edge
    ho = Histogram("big")
    ho.observe(1e9)
    assert ho.quantile(0.99) == DEFAULT_LATENCY_EDGES[-1]
    # summary contributes the four derived keys
    assert set(h.summary()) == {"lat.count", "lat.sum", "lat.p50", "lat.p99"}


def test_registry_histogram_in_snapshot():
    reg = Registry()
    reg.histogram("edt.execute.step").observe(0.25)
    snap = reg.snapshot()
    assert snap["edt.execute.step.count"] == 1
    assert snap["edt.execute.step.sum"] == pytest.approx(0.25)


# ------------------------------------------------------- Stats as a view


def test_stats_view_is_field_compatible():
    st = Stats()
    st.messages_sent += 7
    st.makespan = 3.5
    assert st.messages_sent == 7
    # the same numbers are visible under the dotted registry names
    assert st.registry.value("runtime.messages_sent") == 7
    assert st.registry.value("runtime.makespan") == 3.5
    snap = st.snapshot()
    assert snap["messages_sent"] == 7
    assert snap["makespan"] == 3.5
    # zero-value types survive (ints stay ints, floats stay floats)
    assert isinstance(snap["tasks_executed"], int)
    assert isinstance(snap["io_overlap_ticks"], float)


def test_runtime_stats_share_registry():
    rt = Runtime()
    assert rt.stats.registry is rt.registry

    def main(paramv, depv, api):
        return NULL_GUID

    spawn_main(rt, main)
    stats = rt.run()
    assert rt.registry.value("runtime.tasks_executed") == \
        stats.tasks_executed > 0


# ------------------------------------------- determinism vs committed benches


def test_contention_bench_bit_identical_with_monitoring(monkeypatch):
    """The PR 9 sanitize=off contract applied to monitoring hooks: with
    REPRO_MONITOR=1 every virtual metric of bench_contention matches the
    committed (monitor-off) snapshot bit for bit."""
    monkeypatch.setenv("REPRO_MONITOR", "1")
    from benchmarks.bench_contention import _contend
    stats, _wall = _contend(256)
    want = _snapshot("contention")
    assert stats.makespan == want["makespan"]
    assert stats.messages_sent == want["messages_sent"]
    assert stats.waiter_wakeups == want["waiter_wakeups"]


def test_serve_bench_bit_identical_with_monitoring():
    """bench_serve runs its engines with monitor=True; every virtual
    metric must match the committed snapshot exactly, and the new
    histogram-sourced p99 keys must be populated."""
    from benchmarks.bench_serve import _LOADS, _head_to_head
    cont, stat = _head_to_head(*_LOADS[0][1:])
    want = _snapshot("serve")
    assert cont["makespan_s"] == want["makespan_continuous"]
    assert cont["tok_per_s"] == want["tok_per_s_continuous"]
    assert cont["p99_latency_s"] == want["p99_latency_s_continuous"]
    assert stat["p99_latency_s"] == want["p99_latency_s_static"]
    assert cont["creator_calls"] == want["creator_calls"]
    assert cont["p99_hist_latency_s"] == want["p99_hist_latency_s_continuous"]
    assert cont["p99_hist_ttft_s"] == want["p99_hist_ttft_s_continuous"]
    assert cont["p99_hist_latency_s"] > 0.0


def test_virtual_snapshots_bit_identical_while_profiling(tmp_path):
    """Wall-clock spans record while a JAX profiler trace runs; they touch
    no virtual clock, so bench_serve and bench_contention read the
    committed snapshots exactly with recording on."""
    import jax
    from benchmarks.bench_contention import _contend
    from benchmarks.bench_serve import _LOADS, _head_to_head
    from repro.monitoring import spans
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats, _wall = _contend(256)
        cont, stat = _head_to_head(*_LOADS[0][1:])
    finally:
        jax.profiler.stop_trace()
    names = {s.name for s in spans.recorded()}
    assert {"ocr.run", "serve.iteration", "serve.decode"} <= names
    want = _snapshot("contention")
    assert stats.makespan == want["makespan"]
    assert stats.messages_sent == want["messages_sent"]
    assert stats.waiter_wakeups == want["waiter_wakeups"]
    want = _snapshot("serve")
    assert cont["makespan_s"] == want["makespan_continuous"]
    assert cont["tok_per_s"] == want["tok_per_s_continuous"]
    assert cont["p99_latency_s"] == want["p99_latency_s_continuous"]
    assert stat["p99_latency_s"] == want["p99_latency_s_static"]
    assert cont["creator_calls"] == want["creator_calls"]
    assert cont["p99_hist_latency_s"] == want["p99_hist_latency_s_continuous"]
    assert cont["p99_hist_ttft_s"] == want["p99_hist_ttft_s_continuous"]


# ------------------------------------------------- EDT-class histograms


def test_per_edt_class_latency_histograms():
    rt = Runtime(monitor=True)

    def worker(paramv, depv, api):
        return NULL_GUID

    def main(paramv, depv, api):
        db, _ = api.db_create(16)
        api.db_release(db)
        tmpl = api.edt_template_create(worker, 0, 1)
        for _ in range(4):      # serialize in RW: nonzero grant waits
            api.edt_create(tmpl, depv=[db], dep_modes=[DbMode.RW],
                           duration=1.0)
        return NULL_GUID

    spawn_main(rt, main)
    rt.run()
    snap = rt.registry.snapshot("edt.")
    assert snap["edt.execute.worker.count"] == 4
    assert snap["edt.grant_wait.worker.count"] == 4
    # the last waiter waited ~3 time units behind the other three
    assert snap["edt.grant_wait.worker.sum"] > 0.0
    assert snap["edt.execute.main.count"] == 1


# ------------------------------------------------- serve engine: live gauges


def _spill_engine(**kw):
    from repro.serve.engine import ServeEngine, SyntheticBackend
    return ServeEngine(SyntheticBackend(page_size=8), b_cap=8,
                       pool_pages=20, max_pages=6, resident_budget=4,
                       **kw)


def _spill_load():
    from repro.serve.engine import poisson_workload
    return poisson_workload(30, 300.0, prompt_len=(8, 24), gen=(8, 24),
                            seed=1)


def test_mid_run_snapshots_show_live_io():
    """Mid-run snapshot() from inside the serve loop: queue depth and
    inflight IO are live — their values change across snapshots taken
    within one run() (the acceptance criterion)."""
    eng = _spill_engine(monitor_interval=0.005)
    eng.run(_spill_load())
    snaps = eng.monitor_snapshots
    assert len(snaps) >= 3
    inflight = [s["io.inflight_ops"] for s in snaps]
    depth = [s["io.queue_depth"] for s in snaps]
    assert len(set(inflight)) >= 2, inflight
    assert max(inflight) > 0
    assert max(depth) >= 0
    # engine gauges ride the same registry
    assert any(s["serve.active"] > 0 for s in snaps)
    assert all("spill.objects" in s for s in snaps)


def test_engine_monitor_callable_between_runs():
    eng = _spill_engine(monitor=True)
    snap = eng.monitor()
    assert snap["serve.free_slots"] == 8
    assert snap["serve.queued"] == 0
    assert snap["io.inflight_ops"] == 0


# ------------------------------------------------- backpressure admission


def test_backpressure_defers_admission_page_gate_would_accept():
    """With admit_max_inflight_io=0, any in-flight spill/unspill IO
    defers admissions even while pages and slots are free — the
    page/slot-only engine admits the same request earlier."""
    gated = _spill_engine(admit_max_inflight_io=0)
    reqs_g = _spill_load()
    m_gated = gated.run(reqs_g)
    assert gated.deferred_admissions > 0
    assert m_gated["deferred_admissions"] > 0
    # the deferral happened at an instant where page/slot gating alone
    # would have admitted: same workload, no gate, admits strictly
    # earlier for at least one request (and never later for any)
    plain = _spill_engine(monitor=True)
    reqs_p = _spill_load()
    m_plain = plain.run(reqs_p)
    assert plain.deferred_admissions == 0
    firsts_g = {r.rid: r.t_first for r in reqs_g}
    firsts_p = {r.rid: r.t_first for r in reqs_p}
    assert any(firsts_g[rid] > firsts_p[rid] for rid in firsts_g)
    # gating must not lose work
    assert all(len(r.out) == r.gen for r in reqs_g)
    assert m_gated["tokens"] == m_plain["tokens"]
    # the deferred count lands in the serve.* namespace too
    assert gated.monitor()["serve.deferred_admissions"] > 0


# ------------------------------------------------- ckpt registry namespace


def test_ckpt_stats_registry_view(tmp_path):
    from repro import ckpt
    state = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    stats = ckpt.save(str(tmp_path), state, step=1)
    assert stats.committed
    assert stats.chunks_written > 0
    # the view writes through to the ckpt.* namespace of the save
    # runtime's registry — one snapshot shows ckpt.* next to io.*
    snap = stats.registry.snapshot()
    assert snap["ckpt.chunks_written"] == stats.chunks_written
    assert snap["ckpt.committed"] is True
    assert "io.write_ops" in snap
    assert snap["io.write_ops"] == stats.io_write_ops
