"""The trip-count-corrected HLO cost analysis, validated on closed forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyse_text


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile().as_text()


def test_scan_flops_exact():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = analyse_text(_compile(f, sds, sds))
    assert cost.flops / (2 * 128**3 * 10) == pytest.approx(1.0, rel=0.01)


def test_nested_scan_flops_exact():
    def g(x, w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=10)
            return c2, None

        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = analyse_text(_compile(g, sds, sds))
    assert cost.flops / (2 * 128**3 * 50) == pytest.approx(1.0, rel=0.01)


def test_grad_remat_flops_ratio():
    def h(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=10)
        return jnp.sum(out)

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = analyse_text(_compile(jax.grad(h, argnums=1), sds, sds))
    # fwd + recompute + 2 bwd dots = 4x the forward matmul flops
    assert cost.flops / (2 * 128**3 * 10) == pytest.approx(4.0, rel=0.1)


def test_gather_counts_output_not_operand():
    """A gather from a big bank must cost ~2x its OUTPUT, not the bank."""
    def f(bank, idx):
        def body(c, i):
            return c + jnp.sum(bank[i]), None
        out, _ = jax.lax.scan(body, jnp.float32(0), idx)
        return out

    bank = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    idx = jax.ShapeDtypeStruct((8, 2), jnp.int32)
    cost = analyse_text(_compile(f, bank, idx))
    bank_bytes = 512 * 1024 * 4
    # 8 iterations x 2 rows gathered: way below one full bank read per iter
    assert cost.bytes < 2 * bank_bytes


def test_bytes_fused_below_upper():
    def f(x, w):
        return jnp.tanh(x @ w) * 2.0 + 1.0

    sds = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cost = analyse_text(_compile(f, sds, sds))
    assert 0 < cost.bytes <= cost.bytes_upper


@pytest.mark.mesh
def test_collective_bytes_multiply_by_trips():
    """psum inside a scan must count once per iteration."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.hlo_analysis import analyse_text
        mesh = jax.make_mesh((4,), ("data",))
        def inner(x):
            def body(c, _):
                return jax.lax.psum(c, "data") * 0.5, None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out
        fn = shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P())
        sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        txt = jax.jit(fn).lower(sds).compile().as_text()
        cost = analyse_text(txt)
        per = 64 * 64 * 4
        ratio = cost.coll_total / per
        assert 6.5 <= ratio <= 14.5, ratio  # 7 trips (x2 if AR counted in+out)
        print("COLL_OK", ratio)
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "COLL_OK" in out.stdout, out.stderr[-1500:]
