"""The serve adapter on the wall clock, over the program's synthetic
backend: no request is prefilled before it is due, the engine's virtual
step costs add nothing, and every token is stamped."""
import numpy as np

from chipbench.paths.serve import drive, make_engine, wall_clock


def _requests(n, gap):
    from repro.serve.engine import Request
    rng = np.random.default_rng(0)
    return [Request(rid=i, arrival=i * gap,
                    prompt=rng.integers(0, 100, 5 + i % 7).astype(np.int32),
                    gen=3 + i % 4) for i in range(n)]


def _engine():
    from repro.serve.engine import SyntheticBackend
    bk = wall_clock(SyntheticBackend)(16, vocab=100)
    return bk, make_engine(bk, b_cap=4, pool_pages=64, max_pages=4)


def test_no_prefill_before_due_and_no_virtual_cost():
    bk, eng = _engine()
    reqs = _requests(12, 0.02)
    assert (eng.cost.prefill_base, eng.cost.prefill_per_tok,
            eng.cost.decode_base, eng.cost.decode_per_row) == (0, 0, 0, 0)
    t0, t_end = drive(bk, eng, reqs, "arrivals", 0.3, 5.0)
    for r in reqs:
        assert bk.start[r.rid] >= t0 + r.arrival
        assert len(r.out) == r.gen
        assert len(bk.times[r.rid]) == r.gen
        assert bk.times[r.rid] == sorted(bk.times[r.rid])
    # the engine's clock is the wall clock of the last call, nothing more
    last = max(t for ts in bk.times.values() for t in ts)
    assert abs(eng.t - (last - t0)) < 1e-6
    assert eng.t <= t_end - t0


def test_backlog_window_opens_at_the_first_full_batch_and_stops():
    bk, eng = _engine()
    reqs = _requests(400, 0.0)
    for r in reqs:
        r.gen = 40
    t0, t_end = drive(bk, eng, reqs, "backlog", 0.2, 0.0)
    assert bk.w0 is not None and bk.w0 >= t0
    assert t_end >= bk.w0 + 0.2
    assert t_end - (bk.w0 + 0.2) < 0.5
    assert any(len(r.out) < r.gen for r in reqs)
