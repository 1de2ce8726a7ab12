"""Serving cells: ``ServeEngine`` over ``ModelBackend``, on the wall clock.

The engine's own clock is virtual (``StepCost``), so the benchmark runs it
with every cost at zero through ``WallClockBackend``: a ``ModelBackend``
that sleeps until a request is due before prefilling it, stamps every
token on the host clock when the call that made it has returned (each
call ends in a host read of its tokens, so the stamp is device-complete),
and moves the engine's clock to wall time after each call.  The engine's
own latency numbers and registry histograms are never read.

Two windows: ``arrivals`` (open loop: every request of the schedule is due
inside the window; the run drains them, up to ``drain_s`` past the close)
and ``backlog`` (every request is due at once; the window opens at the
first decode step with all rows busy and closes ``--seconds`` later).
After the window the float32 reference scores a sample of the finished
requests, drawn from the seed with the longest among them.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import numpy as np

from chipbench.checks import logit_gap
from chipbench.program import check_layout, model_config
from chipbench.reference import dense
from chipbench.traffic.lengths import rng


class StopWindow(Exception):
    """Raised from inside the engine's loop once the run is over."""


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def wall_clock(base):
    """``base`` (a serve-engine backend class) with every call stamped on
    the host clock and the engine's clock moved to wall time."""

    class WallClockBackend(base):

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.engine = None
            self.t0 = 0.0
            self.deadline = float("inf")
            self.window = "arrivals"
            self.seconds = 0.0
            self.w0 = None
            self.b_cap = 0
            self.start: Dict[int, float] = {}
            self.row_of: Dict[int, int] = {}
            self.times: Dict[int, List[float]] = {}
            self.prefills: List[tuple] = []
            self.decodes: List[tuple] = []
            self.sleeps: List[tuple] = []
            # (kind, start, end, dispatched) of every call
            self.calls: List[tuple] = []
            # when the last jitted call handed back control: the host's
            # share of a call ends there, the wait for the device begins
            self.t_dispatch = 0.0
            for name in ("_prefill", "_decode"):
                if hasattr(self, name):
                    setattr(self, name, self._stamped(getattr(self, name)))

        def _stamped(self, fn):
            def call(*args):
                out = fn(*args)
                self.t_dispatch = time.perf_counter()
                return out
            return call

        def _enter(self):
            if time.perf_counter() > self.deadline:
                raise StopWindow

        def _sync(self, t):
            self.engine.t = max(self.engine.t, t - self.t0)

        def prefill(self, row, req, pages):
            self._enter()
            due = self.t0 + req.arrival
            now = time.perf_counter()
            if now < due:
                with _annotate("cb:idle"):
                    time.sleep(due - now)
                self.sleeps.append((now, time.perf_counter()))
            t1 = time.perf_counter()
            with _annotate("cb:prefill"):
                tok = super().prefill(row, req, pages)
            t2 = time.perf_counter()
            self.prefills.append((t1, t2, len(req.prompt)))
            self.calls.append(("prefill", t1, t2, max(t1, self.t_dispatch)))
            self.start[req.rid] = t1
            self.row_of[req.rid] = row
            self.times[req.rid] = [t2]
            self._sync(t2)
            return tok

        def decode_step(self, page_table, cur_lens, active, tokens, rids):
            self._enter()
            rows = np.nonzero(active)[0]
            ctx = [int(cur_lens[r]) for r in rows]
            ids = [int(rids[r]) for r in rows]
            t1 = time.perf_counter()
            if (self.window == "backlog" and self.w0 is None
                    and len(rows) == self.b_cap):
                self.w0 = t1
                self.deadline = t1 + self.seconds
            with _annotate("cb:decode"):
                nt = super().decode_step(page_table, cur_lens, active,
                                         tokens, rids)
            t2 = time.perf_counter()
            self.decodes.append((t1, t2, ctx))
            self.calls.append(("decode", t1, t2, max(t1, self.t_dispatch)))
            for rid in ids:
                self.times[rid].append(t2)
            self._sync(t2)
            return nt

    return WallClockBackend


def warm_up(bk, b_cap: int, max_pages: int) -> None:
    """Run the prefill and decode programs once at the cell's shapes, with
    every write dropped past the pool."""
    import jax
    import jax.numpy as jnp
    pad, pool = bk.prompt_pad, bk.pool_pages
    # twice: the pools the first calls return are placed as the window's
    # will be, and the window must find those programs compiled too
    for _ in range(2):
        _, _, bk.k_pools, bk.v_pools = jax.block_until_ready(bk._prefill(
            bk.params, bk.k_pools, bk.v_pools,
            jnp.zeros((1, pad), jnp.int32), jnp.int32(1),
            jnp.full((pad // bk.page,), pool, jnp.int32)))
        _, _, bk.k_pools, bk.v_pools, _ = jax.block_until_ready(bk._decode(
            bk.params, bk.k_pools, bk.v_pools,
            jnp.asarray(np.full((b_cap, max_pages), pool, np.int32)),
            jnp.asarray(np.zeros(b_cap, np.int32)),
            jnp.asarray(np.zeros(b_cap, bool)),
            jnp.asarray(np.zeros(b_cap, np.int32))))


def sample(reqs, row_of: Dict[int, int], seed: int) -> list:
    """Finished requests to check: the longest, and for every batch row
    one of the requests it served, drawn from the seed, so that a fault
    local to one row cannot hide."""
    done = [r for r in reqs if len(r.out) == r.gen]
    if not done:
        return []
    picked = {max(done, key=lambda r: (r.gen, -r.rid)).rid}
    by_row: Dict[int, list] = {}
    for r in done:
        by_row.setdefault(row_of[r.rid], []).append(r.rid)
    g = rng(seed, "sample")
    for row in sorted(by_row):
        picked.add(by_row[row][int(g.integers(len(by_row[row])))])
    return [r for r in done if r.rid in picked]


def reference_gaps(params, dm, picked, t_pad: int, n_out: int,
                   modes=("f32",)) -> Dict[str, float]:
    """Widest logit gap of the served tokens against the float32
    reference (``f32``), and of the tokens a lower-precision reference
    puts first at the same positions (any other mode)."""
    import jax.numpy as jnp
    fns = {m: dense.make_logits_at(dm, m) for m in set(modes) | {"f32"}}
    gaps = {m: 0.0 for m in modes}
    for r in picked:
        plen = len(r.prompt)
        seq = np.zeros(t_pad, np.int32)
        full = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        seq[: len(full)] = full
        idx = np.full(n_out, plen - 1, np.int32)
        idx[: r.gen] = plen - 1 + np.arange(r.gen)
        ref = np.asarray(fns["f32"](params, jnp.asarray(seq),
                                    jnp.asarray(idx)))[: r.gen]
        for m in modes:
            if m == "f32":
                chosen = np.asarray(r.out)
            else:
                low = np.asarray(fns[m](params, jnp.asarray(seq),
                                        jnp.asarray(idx)))[: r.gen]
                chosen = low.argmax(axis=1)
            gaps[m] = max(gaps[m], logit_gap(ref, chosen))
    return gaps


def stalls(bk, requests, t0) -> dict:
    """Where a run's longest waits were: the longest prefill and decode
    calls, the five slowest calls of all (when each began, its length,
    and the host's share of it before the device program was handed
    over), the longest host stretch between two calls (waits for
    arrivals left out), and when the three slowest first tokens were
    due."""
    calls = sorted((a, b, k, d) for k, a, b, d in bk.calls)
    asleep = bk.sleeps
    gaps = [c[0] - p[1] for p, c in zip(calls, calls[1:])
            if not any(p[1] <= s <= c[0] for s, _ in asleep)]
    slow = sorted((q["times"][0] - q["due"], q["due"] - t0)
                  for q in requests if q["times"])[-3:]
    slow_calls = sorted(calls, key=lambda c: c[1] - c[0])[-5:]
    return {"longest_prefill_ms": 1e3 * max((c[1] - c[0] for c in bk.prefills),
                                            default=0.0),
            "longest_decode_ms": 1e3 * max((c[1] - c[0] for c in bk.decodes),
                                           default=0.0),
            "slowest_calls": [{"kind": k, "at_s": a - t0,
                               "ms": 1e3 * (b - a), "host_ms": 1e3 * (d - a)}
                              for a, b, k, d in slow_calls],
            "longest_host_gap_ms": 1e3 * max(gaps, default=0.0),
            "slowest_ttft_ms_at_due_s": [[1e3 * w, d] for w, d in slow]}


def make_engine(bk, b_cap: int, pool_pages: int, max_pages: int):
    """The engine over a wall-clock backend, its virtual step costs at
    zero."""
    from repro.serve.engine import ServeEngine, StepCost
    eng = ServeEngine(bk, b_cap=b_cap, pool_pages=pool_pages,
                      max_pages=max_pages, cost=StepCost(0.0, 0.0, 0.0, 0.0))
    bk.engine, bk.b_cap = eng, b_cap
    return eng


def drive(bk, eng, reqs, window: str, seconds: float, drain_s: float,
          traced=None):
    """Run the engine over ``reqs`` from now: returns (t0, t_end) on the
    host clock.  Under ``arrivals`` the run ends when every request is
    done or ``drain_s`` after the window; under ``backlog`` when the
    window that opens with the first full batch closes."""
    bk.window, bk.seconds = window, seconds
    t0 = time.perf_counter()
    bk.t0 = t0
    if window == "arrivals":
        bk.deadline = t0 + seconds + drain_s
    with traced or contextlib.nullcontext():
        try:
            eng.run(reqs)
        except StopWindow:
            pass
    return t0, time.perf_counter()


def run(ctx) -> dict:
    import jax.numpy as jnp
    from repro.models.model import LanguageModel
    from repro.serve.engine import ModelBackend, Request

    tf, lim = ctx.traffic, ctx.limits
    run_cfg = ctx.config["run"]["serve"]
    model = LanguageModel(model_config(ctx.config, run_cfg))
    dm = dense.Dims.from_config(ctx.config)
    params = dense.init_params(dm, ctx.seed32,
                               jnp.dtype(run_cfg["param_dtype"]))
    check_layout(params, model)

    b_cap, max_pages = tf["b_cap"], tf["max_pages"]
    bk = wall_clock(ModelBackend)(model, params, pool_pages=tf["pool_pages"],
                                  page_size=tf["page"],
                                  prompt_pad=tf["prompt_pad"])
    warm_up(bk, b_cap, max_pages)
    eng = make_engine(bk, b_cap, tf["pool_pages"], max_pages)
    reqs = [Request(rid=q["rid"], arrival=q["due"], prompt=q["prompt"],
                    gen=q["gen"]) for q in ctx.generate()]
    cap = min(max_pages * tf["page"],
              ctx.config.get("sliding_window") or 1 << 30)
    for r in reqs:
        if len(r.prompt) > tf["prompt_pad"] or len(r.prompt) + r.gen > cap:
            raise ValueError(f"request {r.rid} does not fit the cell's "
                             f"prompt_pad, page table or window")
    setup_s = time.perf_counter() - ctx.t_start
    t0, t_end = drive(bk, eng, reqs, tf["window"], ctx.seconds,
                      tf.get("drain_s", 0.0), ctx.window())
    if tf["window"] == "arrivals":
        w0, w1 = t0, t0 + ctx.seconds
    else:
        if bk.w0 is None or t_end < bk.deadline:
            raise RuntimeError("the backlog emptied before the window closed")
        w0, w1 = bk.w0, bk.w0 + ctx.seconds
    peak = ctx.memory_peak()

    requests = [{"rid": r.rid, "due": t0 + r.arrival, "plen": len(r.prompt),
                 "gen": r.gen, "start": bk.start.get(r.rid),
                 "times": bk.times.get(r.rid, []),
                 "done": len(r.out) == r.gen} for r in reqs]
    if tf["window"] == "arrivals":
        attempted = len(reqs)
        failed = sum(1 for q in requests if not q["done"])
    else:
        attempted = sum(1 for q in requests if q["start"] is not None)
        failed = 0
    rec = {
        "kind": "serve", "setup_s": setup_s, "window": (w0, w1),
        "t0": t0, "t_end": t_end, "requests": requests,
        "prefills": bk.prefills, "decodes": bk.decodes, "sleeps": bk.sleeps,
        "dims": dm, "weight_itemsize": jnp.dtype(run_cfg["param_dtype"]).itemsize,
        "kv_itemsize": jnp.dtype(run_cfg["dtype"]).itemsize,
        "memory_peak_bytes": peak, "attempted": attempted, "failed": failed,
        "readings": {"evictions": eng.evictions, **stalls(bk, requests, t0)},
    }

    # the program's state goes before the reference runs
    picked = sample(reqs, bk.row_of, ctx.seed)
    bk.k_pools = bk.v_pools = None
    del eng, bk
    n_out = tf["output"]["max"]
    t_ref = time.perf_counter()
    gaps = reference_gaps(params, dm, picked, tf["prompt_pad"] + n_out, n_out,
                          ctx.modes)
    rec["control"] = {m: {"served_logit_gap": g} for m, g in gaps.items()
                      if m != "f32"}
    rec["readings"].update(sampled=len(picked),
                           sampled_tokens=sum(r.gen for r in picked),
                           reference_s=time.perf_counter() - t_ref)
    rec["checks"] = [{"name": "served_logit_gap", "value": gaps["f32"],
                      "limit": lim["served_logit_gap"]},
                     {"name": "unsampled", "value": 0 if picked else 1,
                      "limit": 0}]
    return rec
