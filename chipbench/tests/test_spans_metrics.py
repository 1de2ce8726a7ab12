"""The readers of the program's own spans, on hand-made spans and stamps,
on a copy of the recorded trace cut into three calls, and on a whole run
of each cell at a size the CPU holds."""
import copy
import itertools
import json
import os
import sys
import types

import pytest

from chipbench import run as R
from chipbench import spans as S
from chipbench.tests import tiny
from repro.monitoring.spans import Span

NEW = ["runtime_ms", "runtime_ms.batch", "runtime_ms.train",
       "engine_self_ms", "engine_self_ms.batch", "launch_ms",
       "launch_ms.batch", "input_ms.train", "step_host_ms.train"]
US = 1e-6
RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def metric(name):
    return R.load_module(os.path.join(R.BENCH, "metrics", name + ".py")).read


class Book:
    """Hand-made spans on the perf-counter clock, nested as opened."""

    def __init__(self):
        self.spans, self.stack, self.ids = [], [], itertools.count(1000)

    def add(self, name, start, end, kids=(), **attrs):
        sid = next(self.ids)
        parent = self.stack[-1] if self.stack else -1
        self.stack.append(sid)
        for k in kids:
            k()
        self.stack.pop()
        self.spans.append(Span(name, start, end, parent, attrs, sid))


def calls_trace():
    """The recorded trace's operations cut into three calls, each moved
    2 ms after the one before, and the program's spans of the three
    decode iterations that made them.  Each call's dispatch starts
    (300, 500, 700) us before its first operation, on a perf-counter
    clock that reads 100 s at the trace's first operation."""
    with open(RECORDED) as f:
        ops = sorted(copy.deepcopy(json.load(f)["events"]),
                     key=lambda e: e["start_ns"])
    third = len(ops) // 3
    chunks = [ops[:third], ops[third:2 * third], ops[2 * third:]]
    for k, chunk in enumerate(chunks):
        for e in chunk:
            e["start_ns"] += k * 2e6
    offset = chunks[0][0]["start_ns"] - 100e9
    book, events, decodes = Book(), [], []
    for k, (chunk, lead) in enumerate(zip(chunks, (300, 500, 700))):
        first = (chunk[0]["start_ns"] - offset) / 1e9
        last = max(e["start_ns"] + e["dur_ns"] for e in chunk)
        end = (last - offset) / 1e9
        d = first - lead * US
        # the path stamps its call 10 us before the program's dispatch,
        # give or take a microsecond; the trace's event starts at 10 us
        t1 = d - 10 * US + (1 - k) * US
        decodes.append((t1, end + 12 * US, [5]))
        events.append({"plane": "/host:CPU", "line": "python",
                       "name": "cb:decode",
                       "start_ns": 1e9 * (d - 10 * US) + offset,
                       "dur_ns": 1e9 * (end - d + 20 * US)})
        book.add("serve.iteration", d - 50 * US, end + 20 * US, kids=[
            lambda: book.add("ocr.run", d - 45 * US, d - 25 * US, tasks=2),
            lambda: book.add("serve.decode", d - 15 * US, end + 10 * US,
                             rows=5, kids=[
                lambda: book.add("serve.dispatch", d, d + 100 * US),
                lambda: book.add("serve.readback", d + 100 * US,
                                 end + 5 * US)])])
    rec = {"window": (99.0, 101.0), "decodes": decodes, "prefills": []}
    return ops + events, book.spans, rec, offset


@pytest.fixture
def workdir(monkeypatch, tmp_path):
    """A whole run's token file and trace in a directory of its own, so
    that runs of other test files at the same time cannot swap them."""
    monkeypatch.setattr(R, "WORK_DIR", str(tmp_path))


@pytest.fixture
def recorded(monkeypatch):
    """Hand the readers a list of spans in place of the program's."""
    box = []
    monkeypatch.setattr(S, "program_spans", lambda: list(box))
    return box


def test_clock_offset_is_the_median_over_calls():
    events, _, rec, offset = calls_trace()
    assert S.clock_offset_ns(events, rec) == pytest.approx(offset, abs=1)
    # a trace that lost a call's event pairs nothing of that kind
    assert S.clock_offset_ns(events[:-1], rec) is None


def test_serve_readers_on_three_calls(recorded):
    events, spans, rec, _ = calls_trace()
    recorded.extend(spans)
    ctx = types.SimpleNamespace(trace_events=events)
    assert metric("launch_ms")(rec, ctx) == pytest.approx(0.5, abs=1e-6)
    assert metric("launch_ms.batch")(rec, ctx) == metric("launch_ms")(rec, ctx)
    # iteration less its decode: 35 us before it and 10 us after
    assert metric("engine_self_ms")(rec, ctx) == pytest.approx(0.045)
    assert metric("runtime_ms")(rec, ctx) == pytest.approx(0.020)
    assert metric("runtime_ms.batch")(rec, ctx) == pytest.approx(0.020)
    # a window that closes after the first call: its own pair sets the
    # offset, whose stamp is 1 us late, and the calls after the close
    # (an arrival window's drain) are read as ``engine_host_ms`` reads them
    first = dict(rec, window=(99.0, 100.0))
    assert len(S.named(S.window_spans(first, spans), "serve.decode")) == 3
    assert metric("launch_ms")(first, ctx) == pytest.approx(0.501, abs=1e-6)
    assert metric("engine_self_ms")(first, ctx) == pytest.approx(0.045)
    # a window that opens after the first call leaves its spans out
    later = dict(rec, window=(rec["decodes"][1][0] - 60 * US, 101.0))
    assert len(S.named(S.window_spans(later, spans), "serve.decode")) == 2


def test_idle_split_by_the_deepest_span():
    events, spans, rec, offset = calls_trace()
    got = S.idle_by_span(events, spans, offset)
    # two 2-ms holes between the calls; each is covered from the call's
    # end by readback 5, decode 5, iteration 10 us, and before the next
    # call by iteration 5+10, ocr.run 20, decode 15, dispatch 100 us and
    # readback up to the first operation.  The recorded stretch's own
    # holes (a few hundred ns) fall inside the calls' read-backs.
    busy = S.device_ops(events)
    within = sum(b[0] - a[1] for a, b in zip(busy, busy[1:])
                 if b[0] - a[1] < 1e6) / 1e9
    assert 0 < within < 2 * US
    by = got["by_span_s"]
    assert by["serve.dispatch"] == pytest.approx(200 * US, rel=1e-6)
    assert by["ocr.run"] == pytest.approx(40 * US, rel=1e-6)
    assert by["serve.readback"] == pytest.approx(
        (5 + 400) * US + (5 + 600) * US + within, rel=1e-6)
    assert by["serve.iteration"] == pytest.approx(2 * 25 * US, rel=1e-6)
    assert by["serve.decode"] == pytest.approx(2 * 20 * US, rel=1e-6)
    covered = sum(by.values())
    assert covered == pytest.approx((200 + 40 + 1010 + 50 + 40) * US
                                    + within, rel=1e-6)
    assert got["covered_share"] == pytest.approx(covered / got["idle_s"])
    assert got["longest_gap_s"] == pytest.approx(2e-3, rel=0.01)
    assert got["longest_gap_by_span_s"]["serve.readback"] > 0


def train_spans():
    """Two steps inside the trainer's runtime call, [0, 10] s."""
    book = Book()

    def step(t):
        return lambda: book.add("train.step", t, t + 3.0, step=int(t), kids=[
            lambda: book.add("train.input", t, t + 0.004, kids=[
                lambda: book.add("ocr.run", t + 0.001, t + 0.003, tasks=4)]),
            lambda: book.add("train.h2d", t + 0.004, t + 0.005),
            lambda: book.add("train.dispatch", t + 0.005, t + 0.006),
            lambda: book.add("train.wait", t + 0.006, t + 2.99),
            lambda: book.add("train.record", t + 2.99, t + 3.0)])

    book.add("ocr.run", 0.0, 10.0, kids=[step(1.0), step(5.0)], tasks=2)
    return book.spans


def test_train_readers(recorded):
    recorded.extend(train_spans())
    rec, ctx = {"steps": 2}, types.SimpleNamespace(trace_events=None)
    # the trainer's runtime: 10 s less two steps of 3 s; each read's
    # runtime 2 ms
    assert metric("runtime_ms.train")(rec, ctx) == pytest.approx(
        1e3 * (4.0 + 2 * 0.002) / 2)
    assert metric("input_ms.train")(rec, ctx) == pytest.approx(4.0)
    assert metric("step_host_ms.train")(rec, ctx) == pytest.approx(
        1e3 * (3.0 - 2.984))


def test_an_older_traced_run_is_left_out(recorded):
    old = [s._replace(start=s.start - 100, end=s.end - 100, sid=s.sid + 50)
           for s in train_spans()]
    old = [s._replace(parent=s.parent + 50 if s.parent >= 0 else -1)
           for s in old]
    recorded.extend(old + train_spans())
    assert metric("input_ms.train")({"steps": 2}, None) == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW)
def test_no_spans_read_none(recorded, name):
    events, _, rec, _ = calls_trace()
    ctx = types.SimpleNamespace(trace_events=events)
    assert metric(name)(dict(rec, steps=2), ctx) is None


def test_a_program_without_spans_gives_none(monkeypatch):
    import repro.monitoring
    monkeypatch.delattr(repro.monitoring, "spans")
    monkeypatch.setitem(sys.modules, "repro.monitoring.spans", None)
    assert S.program_spans() == []


@pytest.mark.parametrize("cell", ["serve.smollm-360m.chat",
                                  "train.smollm-360m.s4096"])
def test_traced_run_reports_the_span_metrics(cell, workdir):
    """On the CPU the trace has no TPU plane, so ``launch_ms`` stays out;
    the program-span metrics are read from the run's own spans."""
    out, rec = tiny.execute(cell, 2147483659, seconds=0.5, trace=True)
    got = {k: v["value"] for k, v in out["metrics"].items()}
    if cell.startswith("serve"):
        assert got["engine_self_ms"] > 0 and got["runtime_ms"] > 0
        assert got["runtime_ms"] <= got["engine_self_ms"]
        assert "launch_ms" not in got
    else:
        assert got["input_ms.train"] > 0 and got["runtime_ms.train"] > 0
        assert got["step_host_ms.train"] >= got["input_ms.train"]


def test_train_clock_join_pairs_input_events_with_input_spans():
    recs = train_spans()
    inputs = sorted(s.start for s in recs if s.name == "train.input")
    events = [{"plane": "/host:CPU", "line": "python", "name": "cb:input",
               "start_ns": 1e9 * t + 7e9 + k * 1e3, "dur_ns": 1e6}
              for k, t in enumerate(inputs)]
    assert S.train_offset_ns(events, recs) == pytest.approx(7e9 + 500)
    assert S.train_offset_ns(events[:1], recs) is None


@pytest.mark.parametrize("cell", ["serve.h2o-danube3-4b.batch",
                                  "train.smollm-360m.s4096"])
def test_clock_joins_and_idle_split_on_a_tiny_run(cell, workdir,
                                                   monkeypatch):
    """The clock joins pair every call or read of a whole traced run with
    its trace event, and the idle split runs on its trace; the CPU trace
    has no device plane, so it finds no idle."""
    from chipbench import trace
    kept, load = [], trace.load_xplane
    monkeypatch.setattr(trace, "load_xplane",
                        lambda d: kept.append(load(d)) or kept[-1])
    out, rec = tiny.execute(cell, 2147483671, seconds=0.5, trace=True)
    assert out["correct"]
    events, recs = kept[-1], S.program_spans()
    if cell.startswith("serve"):
        offset = S.clock_offset_ns(events, rec)
        inside = S.window_spans(rec, recs)
        # the backlog's window opens inside its first decode call, whose
        # span started just before
        calls = sum(1 for a, *_ in rec["decodes"] if a >= rec["window"][0])
        assert len(S.named(inside, "serve.decode")) in (calls - 1, calls)
        assert len(S.named(inside, "serve.dispatch")) >= calls - 1
    else:
        offset = S.train_offset_ns(events, recs)
        steps = S.named(S.window_spans(rec, recs), "train.step")
        assert len(steps) == rec["steps"]
    assert offset is not None
    got = S.idle_by_span(events, recs, offset)
    assert got["idle_s"] == 0 and got["covered_share"] is None
