"""Loop-aware HLO analyzer: validated against hand-computable programs."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _compile_and_analyze(py_src: str, n_dev: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", py_src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    return out.stdout


def test_scan_flops_counted_with_trip_count():
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.hlo_analysis import analyze_hlo
        def f(x, w):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=11)
            return h
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((32, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        cost = analyze_hlo(c.as_text(), default_group=1)
        expect = 2 * 32 * 64 * 64 * 11
        print("FLOPS", cost.flops, expect)
        assert cost.flops == expect, (cost.flops, expect)
        assert cost.max_trip == 11
    """)
    out = _compile_and_analyze(src, n_dev=1)
    assert "FLOPS" in out


def test_collectives_counted_per_iteration():
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        def f(x, w):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=5)
            return h
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                     NamedSharding(mesh, P("data", "model")))
            ).lower(jax.ShapeDtypeStruct((64, 128), jnp.float32),
                    jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
        cost = analyze_hlo(c.as_text(), default_group=4)
        total = sum(cost.coll_counts.values())
        print("COLLS", cost.coll_counts)
        assert total >= 5, cost.coll_counts   # per-iteration gather x trips
    """)
    out = _compile_and_analyze(src, n_dev=4)
    assert "COLLS" in out


def test_dus_charged_as_slice_not_buffer():
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.hlo_analysis import analyze_hlo
        def f(x):
            def body(buf, i):
                return jax.lax.dynamic_update_index_in_dim(
                    buf, jnp.ones((128,)) * i, i, 0), None
            buf, _ = jax.lax.scan(body, x, jnp.arange(64))
            return buf
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile()
        cost = analyze_hlo(c.as_text(), default_group=1)
        # 64 iterations x 2 * 512B slice  ~= 64KB, NOT 64 x 32KB = 2MB
        print("BYTES", cost.hbm_bytes)
        assert cost.hbm_bytes < 1e6, cost.hbm_bytes
    """)
    out = _compile_and_analyze(src, n_dev=1)
    assert "BYTES" in out


def test_parse_shapes_and_groups():
    from repro.launch.hlo_analysis import _shape_bytes, _group_size
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("(f32[8]{0}, bf16[4,4]{1,0})") == 32 + 32
    assert _group_size("replica_groups=[2,128]<=[256]", 1) == 128
    assert _group_size("replica_groups={{0,1,2,3}}", 1) == 4
    assert _group_size("no groups here", 7) == 7
