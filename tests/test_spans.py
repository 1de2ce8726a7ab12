"""Wall-clock spans (``repro.monitoring.spans``): recorded only while a
JAX profiler trace runs, nested by layer, and written into the trace too."""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro.monitoring import spans
from repro.serve.engine import ServeEngine, SyntheticBackend, poisson_workload


@contextlib.contextmanager
def profiled(trace_dir):
    spans.clear()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_names(trace_dir):
    path = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in pd.planes for line in plane.lines
            for ev in line.events}


def synthetic_run():
    reqs = poisson_workload(6, rate=400.0, prompt_len=(4, 9), gen=(2, 5),
                            seed=5)
    eng = ServeEngine(SyntheticBackend(page_size=4), b_cap=2, pool_pages=12,
                      max_pages=4)
    eng.run(reqs)
    return reqs


def by_sid(recs):
    return {s.sid: s for s in recs}


def ancestors(s, index):
    out = []
    while s.parent in index:
        s = index[s.parent]
        out.append(s.name)
    return out


def test_nothing_recorded_with_profiler_off():
    spans.clear()
    assert not spans.enabled()
    synthetic_run()
    assert spans.recorded() == [] and spans.dropped() == 0


def test_engine_span_tree(tmp_path):
    with profiled(tmp_path):
        reqs = synthetic_run()
    recs = spans.recorded()
    index = by_sid(recs)
    names = [s.name for s in recs]
    for s in recs:
        assert s.start <= s.end
        if s.parent >= 0:
            p = index[s.parent]
            assert p.start <= s.start and s.end <= p.end

    admits = [s for s in recs if s.name == "serve.admit"]
    assert sorted(s.attrs["rid"] for s in admits) == [r.rid for r in reqs]
    for s in admits:
        assert index[s.parent].name == "serve.iteration"
    prefills = [s for s in recs if s.name == "serve.prefill"]
    assert len(prefills) == len(reqs)
    assert all(index[s.parent].name == "serve.admit" for s in prefills)
    # the runtime's flushes sit under the loop and under admissions
    runs = [s for s in recs if s.name == "ocr.run"]
    parents = {index[s.parent].name for s in runs if s.parent >= 0}
    assert {"serve.iteration", "serve.admit"} <= parents
    assert sum(s.attrs["tasks"] for s in runs) > 0
    # every decoded row is counted once on its decode span
    decodes = [s for s in recs if s.name == "serve.decode"]
    assert all(index[s.parent].name == "serve.iteration" for s in decodes)
    assert sum(s.attrs["rows"] for s in decodes) == sum(
        r.gen - 1 for r in reqs)
    iters = [s for s in recs if s.name == "serve.iteration"]
    assert all(s.parent == -1 for s in iters)
    assert all({"queued", "active", "free_pages"} <= set(s.attrs)
               for s in iters if any(d.parent == s.sid for d in decodes))
    assert {"serve.pages"} <= set(names)
    # the same names are in the profiler's own trace
    assert {"serve.iteration", "serve.admit", "serve.prefill",
            "serve.decode", "serve.pages", "ocr.run"} <= xplane_names(tmp_path)


def test_buffer_cap_counts_dropped(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "_capacity", 3)
    with profiled(tmp_path):
        for i in range(5):
            with spans.span("serve.iteration", i=i):
                pass
    assert [s.attrs["i"] for s in spans.recorded()] == [0, 1, 2]
    assert spans.dropped() == 2
    spans.clear()
    assert spans.recorded() == [] and spans.dropped() == 0


def test_threads_keep_their_own_stacks_and_the_cap(tmp_path, monkeypatch):
    """More recording threads than cores, switching often: every span is
    kept or counted as dropped, the cap holds, and each span's parent is
    its own thread's enclosing span."""
    import sys
    import threading
    workers, each, cap = (os.cpu_count() or 1) + 3, 400, 1000
    monkeypatch.setattr(spans, "_capacity", cap)
    go = threading.Barrier(workers)

    def record(w):
        go.wait(timeout=30)
        for i in range(each // 2):
            with spans.span("serve.iteration", w=w, i=i):
                with spans.span("serve.decode", w=w, i=i):
                    pass

    threads = [threading.Thread(target=record, args=(w,))
               for w in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with profiled(tmp_path):
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    recs = spans.recorded()
    assert len(recs) == cap
    assert len(recs) + spans.dropped() == workers * each
    index = by_sid(recs)
    assert len(index) == cap
    for s in recs:
        if s.name == "serve.decode" and s.parent in index:
            assert index[s.parent].attrs == s.attrs
        if s.name == "serve.iteration":
            assert s.parent == -1


def test_span_recorded_when_its_body_raises(tmp_path):
    with profiled(tmp_path):
        with pytest.raises(KeyError):
            with spans.span("serve.iteration"):
                with spans.span("serve.decode"):
                    raise KeyError
        with spans.span("serve.admit"):
            pass
    recs = {s.name: s for s in spans.recorded()}
    assert recs["serve.decode"].parent == recs["serve.iteration"].sid
    assert recs["serve.admit"].parent == -1      # the stack unwound


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_config
    from repro.models.model import LanguageModel
    cfg = get_config("smollm-360m").reduced()
    model = LanguageModel(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_model_backend_dispatch_and_readback(tiny, tmp_path):
    """Each backend call splits into dispatch and read-back; eviction and
    resume carry the request id; the jitted programs have stable names."""
    from repro.serve.engine import ModelBackend, Request
    cfg, model, params = tiny
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, arrival=1e-4 * i,
                    prompt=rng.randint(0, cfg.vocab_size, n).astype(np.int32),
                    gen=8) for i, n in enumerate((10, 7, 12))]
    bk = ModelBackend(model, params, pool_pages=4, page_size=8, prompt_pad=16)
    assert (bk._prefill.__name__, bk._decode.__name__) == (
        "serve_prefill", "serve_decode")
    eng = ServeEngine(bk, b_cap=3, pool_pages=4, max_pages=4,
                      resident_budget=2)
    with profiled(tmp_path):
        eng.run(reqs)
    assert eng.evictions > 0
    recs = spans.recorded()
    index = by_sid(recs)
    calls = [s for s in recs if s.name in ("serve.prefill", "serve.decode")]
    for c in calls:
        kids = [s.name for s in recs if s.parent == c.sid]
        assert kids == ["serve.dispatch", "serve.readback"], kids
    evicts = [s for s in recs if s.name == "serve.evict"]
    resumes = [s for s in recs if s.name == "serve.resume"]
    assert len(evicts) == eng.evictions
    assert {s.attrs["rid"] for s in resumes} == {s.attrs["rid"]
                                                 for s in evicts}
    # a session is evicted to free pages for another
    assert all("serve.pages" in ancestors(s, index) for s in evicts)
    assert {"serve.dispatch", "serve.readback", "serve.evict",
            "serve.resume"} <= xplane_names(tmp_path)


def test_trainer_step_spans(tiny, tmp_path):
    from repro.data import FileTokens
    from repro.data.pipeline import write_token_file
    from repro.optim import OptimizerConfig
    from repro.train.trainer import Trainer, TrainerConfig
    cfg, model, _ = tiny
    batch, seq = 2, 16
    raw = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(4 * batch * (seq + 1),), dtype=np.int32)
    path = str(tmp_path / "tokens.bin")
    write_token_file(path, raw)
    tr = Trainer(model, OptimizerConfig(), FileTokens(path, cfg.vocab_size,
                                                      batch, seq),
                 TrainerConfig())
    state = tr.run(tr.init_or_restore(jax.random.PRNGKey(0)), 1)
    with profiled(tmp_path / "trace"):
        tr.run(state, 3, start_step=1)
    recs = spans.recorded()
    index = by_sid(recs)
    steps = [s for s in recs if s.name == "train.step"]
    assert [s.attrs["step"] for s in steps] == [1, 2, 3]
    for st in steps:
        kids = [s.name for s in recs if s.parent == st.sid]
        assert kids == ["train.input", "train.h2d", "train.dispatch",
                        "train.wait", "train.record"]
        # the step task runs inside the trainer's runtime
        assert index[st.parent].name == "ocr.run"
    # FileTokens reads each batch through its own runtime
    reads = [s for s in recs if s.name == "ocr.run"
             and index.get(s.parent, s).name == "train.input"]
    assert len(reads) == 3 and all(s.attrs["tasks"] >= 2 for s in reads)
    assert "train.wait" in xplane_names(tmp_path / "trace")
