"""The trip-count-aware HLO cost parser vs known-cost programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_dot_flops_exact():
    m, k, n = 128, 256, 64
    f = lambda a, b: a @ b
    c = _compile(f, jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32))
    cost = hlo_cost.analyze(c.as_text())
    assert cost.flops == 2 * m * k * n


def test_scan_trip_count_multiplies():
    def mk(nlayers):
        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y
        return _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                        jax.ShapeDtypeStruct((nlayers, 64, 64), jnp.float32))

    c2 = hlo_cost.analyze(mk(2).as_text())
    c8 = hlo_cost.analyze(mk(8).as_text())
    assert c8.flops == pytest.approx(4 * c2.flops, rel=1e-6)
    # XLA's own cost_analysis counts the body once (the bug we fix).
    # jax < 0.5 returns a one-element list of dicts, newer a dict.
    def raw_flops(compiled):
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return ca["flops"]

    assert raw_flops(mk(2)) == raw_flops(mk(8))


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(c, wg):
            def inner(c2, w):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, wg)
            return c, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y
    c = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                 jax.ShapeDtypeStruct((3, 5, 32, 32), jnp.float32))
    cost = hlo_cost.analyze(c.as_text())
    assert cost.flops == pytest.approx(15 * 2 * 32 * 32 * 32, rel=1e-6)


def test_bytes_scale_with_trip_count():
    def mk(n):
        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c * w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y
        return _compile(f, jax.ShapeDtypeStruct((256, 256), jnp.float32),
                        jax.ShapeDtypeStruct((n, 256, 256), jnp.float32))
    b2 = hlo_cost.analyze(mk(2).as_text()).bytes
    b8 = hlo_cost.analyze(mk(8).as_text()).bytes
    assert b8 > 3 * b2


def test_collective_parsing_shapes():
    import os
    import subprocess, sys, textwrap
    # needs >1 device: run in a subprocess with forced host devices
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import sys
        sys.path.insert(0, "src")
        from repro.launch import hlo_cost
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        def f(x):
            return jnp.sum(x)
        fn = jax.jit(f, in_shardings=NamedSharding(mesh, P("model")),
                     out_shardings=NamedSharding(mesh, P()))
        c = fn.lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
        cost = hlo_cost.analyze(c.as_text())
        assert cost.coll_counts["all-reduce"] >= 1, cost.coll_counts
        print("OK")
    """)
    env = dict(os.environ)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))),
                         env=env)
    assert "OK" in out.stdout, out.stderr[-2000:]
