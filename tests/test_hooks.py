"""Sharding hooks: no-op without a mesh, divisibility guards, fallbacks."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import hooks


def teardown_function(_fn):
    hooks.clear()


def test_noop_without_mesh():
    hooks.set_activation_sharding(("data",), "model")
    x = jnp.ones((4, 8))
    y = hooks.shard_batch(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # outside any mesh context the constraint must not be inserted
    assert "sharding_constraint" not in str(
        jax.make_jaxpr(hooks.shard_batch)(x))


def test_noop_when_cleared():
    hooks.clear()
    x = jnp.ones((4, 8))
    assert "sharding_constraint" not in str(
        jax.make_jaxpr(hooks.shard_heads)(x))
    assert hooks.data_axis_size() == 1


def test_constraints_inside_mesh(tmp_path):
    """In a subprocess with 8 forced devices, hooks insert constraints with
    correct divisibility behavior."""
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.models import hooks

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))
        hooks.set_activation_sharding(("data",), "model", seq_model=True)
        with jax.set_mesh(mesh):
            def f(x):
                return hooks.shard_batch(x)
            # divisible batch (8 % 4 == 0) and seq (6 % 2 == 0)
            jx = jax.make_jaxpr(f)(jnp.ones((8, 6, 3)))
            assert "sharding_constraint" in str(jx), jx
            # indivisible batch -> no-op
            jx2 = jax.make_jaxpr(f)(jnp.ones((3, 6, 3)))
            assert "sharding_constraint" not in str(jx2), jx2
            # head fallback: 5 heads don't divide 2 -> seq dim constrained
            def g(x):
                return hooks.shard_heads(x, head_dim=2, seq_dim=1)
            jx3 = str(jax.make_jaxpr(g)(jnp.ones((8, 6, 5, 4))))
            assert "sharding_constraint" in jx3, jx3
            assert hooks.data_axis_size() == 4
        print("HOOKS_OK")
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script],
                         env=dict(os.environ, PYTHONPATH=src),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "HOOKS_OK" in out.stdout
