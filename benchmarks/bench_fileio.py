"""§5 — file IO: async IO-queue overlap vs the synchronous baseline,
chunked parallel read/modify/write, write-back coalescing, dirty-only
checkpoint write-back, and the §6-sharded checkpoint path."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np

from repro.core import DbMode, NULL_GUID, Runtime, spawn_main

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


def _rmw(path: str, nbytes: int, chunks: int, writers: int,
         io_mode: str = "async"):
    """Read-modify-write the file through `chunks` §5 chunk data blocks."""
    rt = Runtime(num_nodes=writers, io_latency=2.0, io_mode=io_mode)
    per = nbytes // chunks

    def work(paramv, depv, api):
        arr = depv[0].ptr.view(np.uint32)
        arr *= 3
        api.db_destroy(depv[0].guid)
        return NULL_GUID

    def main(paramv, depv, api):
        f, desc = api.file_open(path, "rb+")

        def after(pv, dv, api2):
            fg = api2.file_get_guid(dv[0].ptr)
            tmpl2 = api2.edt_template_create(work, 0, 1)
            for c in range(chunks):
                ch = api2.file_get_chunk(fg, c * per, per)
                api2.edt_create(tmpl2, depv=[ch], dep_modes=[DbMode.EW],
                                placement=c % writers, duration=4.0)
            api2.file_release(fg)
            return NULL_GUID

        tmpl = api.edt_template_create(after, 0, 1)
        api.edt_create(tmpl, depv=[desc])
        return NULL_GUID

    spawn_main(rt, main)
    return rt.run()


def _scan(io_mode: str, chunks: int = 32, io_latency: float = 2.0,
          duration: float = 3.0):
    """Read-heavy chained scan: task *i* consumes chunk *i*, feeds *i+1*.

    The §5 overlap shape: with the async IO queue, read-ahead streams
    chunk i+1..n while task i computes; the sync baseline pays
    (read + compute) serially per link.
    """
    path = tempfile.mktemp()
    nbytes = 1 << 15
    np.arange(nbytes // 4, dtype=np.uint32).tofile(path)
    rt = Runtime(num_nodes=2, io_latency=io_latency, io_mode=io_mode)
    per = nbytes // chunks
    acc = {"v": 0}

    def work(paramv, depv, api):
        acc["v"] += int(depv[0].ptr.view(np.uint32).sum())
        api.db_destroy(depv[0].guid)
        return NULL_GUID

    def main(paramv, depv, api):
        f, desc = api.file_open(path, "rb")

        def after(pv, dv, api2):
            fg = api2.file_get_guid(dv[0].ptr)
            tmpl2 = api2.edt_template_create(work, 0, 2)
            prev = None
            for c in range(chunks):
                ch = api2.file_get_chunk(fg, c * per, per)
                depv2 = [ch, prev if prev is not None else NULL_GUID]
                _, ev = api2.edt_create(
                    tmpl2, depv=depv2, dep_modes=[DbMode.RO, DbMode.NULL],
                    duration=duration, output_event=True)
                prev = ev
            api2.file_release(fg)
            return NULL_GUID

        tmpl = api.edt_template_create(after, 0, 1)
        api.edt_create(tmpl, depv=[desc])
        return NULL_GUID

    spawn_main(rt, main)
    stats = rt.run()
    os.unlink(path)
    expect = int(np.arange(nbytes // 4, dtype=np.uint64).sum())
    return stats, acc["v"] == expect


_sharded_cache = {}


def _sharded_ckpt():
    """§6-sharded checkpoint on 8 forced host devices (subprocess: the
    XLA device-count flag must be set before any jax import).  Saves a
    NamedSharding tree (no host gather), restores under a 2-device mesh,
    and verifies bit-exactness through the range manifest."""
    if _sharded_cache:
        return _sharded_cache["rec"]
    # a CPU compile by design: the child must never reach for a chip the
    # parent may hold
    code = ("import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            f"import sys\nsys.path.insert(0, {_SRC!r})\n"
            + textwrap.dedent("""
        import json, tempfile, shutil, time
        import numpy as np
        import jax
        from repro.launch.mesh import make_mesh
        from repro import ckpt
        from repro.dist.sharding import ShardCtx, param_shardings

        rng = np.random.default_rng(0)
        tree = {"params": {
            "w_q": rng.normal(size=(64, 8, 16)).astype(np.float32),
            "w_down": rng.normal(size=(256, 64)).astype(np.float32),
            "embedding": rng.normal(size=(128, 64)).astype(np.float32)}}
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        mesh8 = make_mesh((2, 4), ("data", "model"))
        sh8 = param_shardings(shapes, ShardCtx(mesh=mesh8))
        dev = jax.tree_util.tree_map(jax.device_put, tree, sh8)
        tmp = tempfile.mkdtemp()
        t0 = time.perf_counter()
        st = ckpt.save(tmp, dev, 1, num_writers=8)
        wall_ms = (time.perf_counter() - t0) * 1e3
        mesh2 = make_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
        sh2 = param_shardings(shapes, ShardCtx(mesh=mesh2))
        got, _ = ckpt.restore(tmp, shardings=sh2)
        exact = all(
            np.array_equal(tree["params"][k], np.asarray(got["params"][k]))
            for k in tree["params"])
        shutil.rmtree(tmp)
        print(json.dumps({
            "host_gathers": st.host_gathers, "ranges": st.chunks_total,
            "io_write_ops": st.io_write_ops,
            "io_coalesced_writes": st.io_coalesced_writes,
            "makespan": st.makespan, "wall_ms": wall_ms,
            "reshard_exact": bool(exact)}))
    """))
    out = None
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=560)
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        rec = {"error": f"{type(e).__name__}: {e}"}
        if out is not None and out.returncode != 0:
            rec["error"] = (f"exit={out.returncode}: "
                            + out.stderr.strip()[-500:].replace("\n", " | "))
    _sharded_cache["rec"] = rec
    return rec


def _ckpt_dirty():
    from repro import ckpt
    import shutil
    tmp = tempfile.mkdtemp()
    rng = np.random.default_rng(0)
    tree = {"a": rng.normal(size=(256, 256)).astype(np.float32),
            "b": rng.normal(size=(64, 4096)).astype(np.float32)}
    t0 = time.perf_counter()
    s1 = ckpt.save(tmp, tree, 1, chunk_bytes=1 << 14)
    tree["a"][3, :8] = 0  # touch one chunk
    s2 = ckpt.save(tmp, tree, 2, chunk_bytes=1 << 14)
    us = (time.perf_counter() - t0) / 2 * 1e6
    shutil.rmtree(tmp)
    return s1, s2, us


def run():
    rows = []
    nbytes = 1 << 20
    for chunks, writers in ((1, 1), (4, 4), (16, 4), (64, 8)):
        path = tempfile.mktemp()
        np.arange(nbytes // 4, dtype=np.uint32).tofile(path)
        t0 = time.perf_counter()
        stats = _rmw(path, nbytes, chunks, writers)
        us = (time.perf_counter() - t0) / chunks * 1e6
        ok = np.array_equal(np.fromfile(path, np.uint32),
                            np.arange(nbytes // 4, dtype=np.uint32) * 3)
        os.unlink(path)
        rows.append((
            f"fileio.rmw_c{chunks}_w{writers}", f"{us:.0f}",
            f"makespan={stats.makespan:.0f};bytes_rw={stats.file_bytes_read}"
            f"+{stats.file_bytes_written};correct={ok}"))

    # async IO queue vs synchronous baseline on the read-heavy scan
    for mode in ("sync", "async"):
        t0 = time.perf_counter()
        stats, ok = _scan(mode)
        us = (time.perf_counter() - t0) * 1e6 / 32
        overlap = stats.io_overlap_ticks / stats.makespan if stats.makespan \
            else 0.0
        rows.append((
            f"fileio.scan_{mode}", f"{us:.0f}",
            f"makespan={stats.makespan:.0f};overlap_ratio={overlap:.2f};"
            f"reads_inflight_max={stats.io_reads_inflight_max};"
            f"correct={ok}"))

    # dirty-only checkpoint write-back (§5) + write coalescing
    s1, s2, us = _ckpt_dirty()
    rows.append((
        "fileio.ckpt_dirty_skip", f"{us:.0f}",
        f"full={s1.chunks_written}/{s1.chunks_total};"
        f"delta={s2.chunks_written}/{s2.chunks_total};"
        f"coalesced={s1.io_coalesced_writes};write_ops={s1.io_write_ops}"))

    # §6-sharded checkpoint: no host gather, reshard-on-restore bit-exact
    sh = _sharded_ckpt()
    if "error" not in sh:
        rows.append((
            "fileio.ckpt_sharded_8dev", f"{sh['wall_ms'] * 1e3:.0f}",
            f"host_gathers={sh['host_gathers']};ranges={sh['ranges']};"
            f"write_ops={sh['io_write_ops']};"
            f"makespan={sh['makespan']:.0f};"
            f"reshard_exact={sh['reshard_exact']}"))
    else:
        rows.append(("fileio.ckpt_sharded_8dev.SKIP", "0", sh["error"]))
    return rows


def summary():
    """Machine-readable snapshot for BENCH_fileio.json (perf trajectory)."""
    t0 = time.perf_counter()
    sync_stats, _ = _scan("sync")
    async_stats, _ = _scan("async")
    s1, s2, _us = _ckpt_dirty()
    sh = _sharded_ckpt()
    wall = time.perf_counter() - t0
    return {
        "makespan_scan_sync": sync_stats.makespan,
        "makespan_scan_async": async_stats.makespan,
        "scan_overlap_ratio_async": (async_stats.io_overlap_ticks
                                     / async_stats.makespan),
        "scan_reads_inflight_max_async": async_stats.io_reads_inflight_max,
        "ckpt_write_ops": s1.io_write_ops,
        "ckpt_coalesced_writes": s1.io_coalesced_writes,
        "ckpt_delta_chunks_written": s2.chunks_written,
        "sharded_host_gathers": sh.get("host_gathers", -1),
        "sharded_ranges": sh.get("ranges", 0),
        "sharded_reshard_exact": int(bool(sh.get("reshard_exact", False))),
        "makespan_ckpt_sharded": sh.get("makespan", -1.0),
        "wall_time_s": wall,
    }
