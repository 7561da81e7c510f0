"""The trip-count-aware HLO cost walker vs unrolled XLA references.

XLA's own cost_analysis counts while bodies once (demonstrated below) —
the walker must recover the x-trip-count totals, or the roofline tables
are meaningless.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import analyze, parse_module, shape_elems_bytes

W = jnp.zeros((256, 256), jnp.float32)
X = jax.ShapeDtypeStruct((256, 256), jnp.float32)


def _compiled(f):
    return jax.jit(f).lower(X).compile()


def test_xla_undercounts_scan():
    """Pin the XLA behaviour this module exists to fix."""
    def f_scan(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=10)[0]

    def f_once(x):
        return x @ W
    scan_flops = _compiled(f_scan).cost_analysis()["flops"]
    once_flops = _compiled(f_once).cost_analysis()["flops"]
    assert scan_flops < 2 * once_flops    # ~1x, NOT ~10x


def test_scan_flops_match_unroll():
    def f_scan(x):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ W), None), x, None,
                            length=10)[0]

    def f_unroll(x):
        for _ in range(10):
            x = jnp.tanh(x @ W)
        return x
    a_s = analyze(_compiled(f_scan).as_text())
    a_u = analyze(_compiled(f_unroll).as_text())
    assert a_s.unknown_loops == 0
    np.testing.assert_allclose(a_s.flops, a_u.flops, rtol=0.01)
    # dot flops dominate and must match the analytic count
    want = 10 * 2 * 256 ** 3
    np.testing.assert_allclose(a_u.flops, want, rtol=0.02)


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            c2 = jax.lax.scan(lambda q, _: (q @ W, None), c, None,
                              length=5)[0]
            return jnp.tanh(c2), None
        return jax.lax.scan(outer, x, None, length=4)[0]
    a = analyze(_compiled(f).as_text())
    want = 4 * 5 * 2 * 256 ** 3
    np.testing.assert_allclose(a.flops, want, rtol=0.02)
    assert a.unknown_loops == 0


def test_dynamic_while_reported_unknown():
    def f(x):
        def cond(c):
            return jnp.sum(c) < 1e9
        def body(c):
            return c + jnp.abs(c @ W)
        return jax.lax.while_loop(cond, body, x + 1.0)
    a = analyze(_compiled(f).as_text())
    assert a.unknown_loops >= 1


def test_collectives_inside_scan_multiply():
    import os
    import subprocess
    import sys
    # needs >1 device; run in a subprocess with forced host devices so this
    # test process keeps its single-device view
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as PS, NamedSharding, AxisType
import sys
sys.path.insert(0, "src")
from repro.launch.hlo_cost import analyze
mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
def f(x, w):
    def body(c, _):
        y = c @ w                       # TP matmul -> all-reduce per step
        return y, None
    return jax.lax.scan(body, x, None, length=6)[0]
comp = jax.jit(f, in_shardings=(NamedSharding(mesh, PS()),
                                NamedSharding(mesh, PS("model", None)))
               ).lower(x, w).compile()
a = analyze(comp.as_text())
ar = a.collectives.get("all-reduce", {"count": 0})
print(int(ar["count"]))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.getcwd())
    assert out.returncode == 0, out.stderr[-2000:]
    count = int(out.stdout.strip().splitlines()[-1])
    assert count >= 6, f"scanned all-reduce counted {count} times, want >=6"


def test_shape_parse():
    assert shape_elems_bytes("f32[4,8]")[1] == 128
    assert shape_elems_bytes("bf16[10]")[1] == 20
    assert shape_elems_bytes("(f32[2,2], s32[3])")[1] == 28
    assert shape_elems_bytes("pred[]")[1] == 1


def test_parse_module_finds_entry():
    comps = parse_module(_compiled(lambda x: x @ W).as_text())
    assert any(c.startswith("main") for c in comps)
