"""Trip-count-aware HLO analyzer: validated against XLA cost_analysis on
scan-free programs and hand counts on scanned/nested programs; collective
wire bytes on a multi-device subprocess."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import analyze_hlo, wire_bytes


def _compiled(f, *args):
    return jax.jit(f).lower(*args).compile()


def _xla_cost(comp):
    """jaxlib >= 0.4.36 returns a one-element list from cost_analysis()."""
    ca = comp.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca


def test_matches_xla_on_scan_free():
    def f(x, w):
        return jnp.tanh(x @ w) @ w

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    comp = _compiled(f, x, w)
    mine = analyze_hlo(comp.as_text())
    assert mine.flops == _xla_cost(comp)["flops"]


def test_scan_trip_count_multiplication():
    def g(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    comp = _compiled(g, x, w)
    mine = analyze_hlo(comp.as_text())
    assert mine.flops == 2 * 128 ** 3 * 10
    # XLA counts the body once — the whole reason this module exists
    assert _xla_cost(comp)["flops"] < mine.flops


def test_nested_scan():
    def h(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=4)
            return ci, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    mine = analyze_hlo(_compiled(h, x, w).as_text())
    assert mine.flops == 2 * 128 ** 3 * 20


def test_bytes_reasonable_for_simple_matmul():
    def f(x, w):
        return x @ w

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    mine = analyze_hlo(_compiled(f, x, w).as_text())
    expect = 3 * 256 * 256 * 4
    assert expect <= mine.bytes <= 3 * expect


def test_wire_bytes_model():
    assert wire_bytes("all-gather", 1000, 8) == pytest.approx(875.0)
    assert wire_bytes("all-reduce", 1000, 8) == pytest.approx(1750.0)
    assert wire_bytes("reduce-scatter", 1000, 8) == pytest.approx(7000.0)
    assert wire_bytes("collective-permute", 1000, 1) == 1000.0
    assert wire_bytes("all-gather", 1000, 1) == 0.0


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis.hlo import analyze_hlo
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((8,), ("d",))
    x = jax.ShapeDtypeStruct((1024, 512), jnp.float32,
                             sharding=NamedSharding(mesh, P("d", None)))
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "d")))

    def f(x, w):
        return jnp.sum(jnp.square(x @ w))

    comp = jax.jit(f, out_shardings=NamedSharding(mesh, P())).lower(x, w).compile()
    c = analyze_hlo(comp.as_text())
    print(json.dumps({"flops": c.flops, "coll": c.collective_bytes,
                      "kinds": c.collective_by_kind}))
""")


def test_collective_bytes_multi_device():
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # per-device flops: total / 8
    assert res["flops"] == pytest.approx(2 * 1024 * 512 * 512 / 8, rel=0.01)
    # the w all-gather dominates: 512*512*4 * 7/8
    assert res["coll"] == pytest.approx(512 * 512 * 4 * 7 / 8, rel=0.05)
