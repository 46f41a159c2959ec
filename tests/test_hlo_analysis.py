"""Trip-count-aware HLO analysis (the §Roofline data source)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils.hlo import analyze_hlo, _parse_computations


def test_scan_flops_weighted_by_trip_count():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        c, _ = jax.lax.scan(body, x, w)
        return c.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    t = jax.jit(f).lower(x, w).compile().as_text()
    a = analyze_hlo(t)
    assert a.dot_flops == 7 * 2 * 64**3


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return c2 @ wi, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        c, _ = jax.lax.scan(outer, x, w)
        return c.sum()

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    t = jax.jit(f).lower(x, w).compile().as_text()
    a = analyze_hlo(t)
    assert a.dot_flops == 5 * 3 * 2 * 32**3


def test_collectives_counted_with_groups():
    import os
    # needs >1 device; spawn is heavy — reuse existing if multi-device
    if jax.device_count() < 2:
        import subprocess, sys, textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.utils.hlo import analyze_hlo
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("d",))
            def f(x): return x.sum()
            xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
            with mesh:
                c = jax.jit(f, in_shardings=NamedSharding(mesh, P("d", None))).lower(xs).compile()
            a = analyze_hlo(c.as_text())
            assert sum(a.collectives.count.values()) >= 1, a.collectives.count
            print("OK")
        """)
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env={**os.environ, "PYTHONPATH": "src"})
        assert "OK" in out.stdout, out.stderr[-2000:]


def test_parse_computations_finds_entry():
    t = jax.jit(lambda x: x * 2).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    entry, comps = _parse_computations(t)
    assert entry is not None
    assert entry in comps


def test_peaks_keyed_by_device_kind_and_unknown_kind_raises():
    from repro.launch.mesh import TARGET_KIND, peaks

    v5e = peaks(TARGET_KIND)
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bandwidth"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
