"""Dry-run machinery on a miniature mesh, in a subprocess (so the forced
device count never leaks into other tests)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import repro.configs
    from repro.launch import shardings, steps
    from repro.models.base import get_config
    from repro.roofline import analyze_compiled
    from repro.launch.mesh import HW

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                ("data", "model"))

    # smoke config so the mini-mesh compile is fast
    import repro.models.base as base
    cfg = get_config("llama3.2-1b", smoke=True)
    base._REGISTRY["llama3.2-1b"] = lambda smoke=False: cfg

    case = steps.build_case("llama3.2-1b", "train_4k", mesh)
    # shrink the batch to the smoke scale
    def shrink(sds):
        if not hasattr(sds, "shape"):
            return sds
        shape = tuple(min(d, 8) if i == 0 else min(d, 64)
                      for i, d in enumerate(sds.shape))
        return jax.ShapeDtypeStruct(shape, sds.dtype)
    batch = {k: shrink(v) for k, v in case.args_sds[2].items()}
    bspecs = shardings.batch_specs(batch, mesh)
    args = (case.args_sds[0], case.args_sds[1], batch)
    in_sh = shardings.named(mesh, (case.in_shardings[0],
                                   case.in_shardings[1], bspecs))
    with jax.set_mesh(mesh):
        compiled = jax.jit(case.step_fn, in_shardings=in_sh).lower(
            *args).compile()
    rep = analyze_compiled(compiled, arch="llama3.2-1b", shape="train_4k",
                           mesh_name="mini", chips=8, hw=HW,
                           n_params_active=1_000_000, n_tokens=8 * 64,
                           kind="train")
    print("RESULT " + json.dumps(rep.row()))
""")


@pytest.mark.slow
def test_mini_mesh_dryrun_compiles_and_analyzes():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout
    row = json.loads(line[0][7:])
    assert row["hlo_gflops_per_dev"] > 0
    assert row["t_compute_s"] >= 0
    assert row["dominant"] in ("compute", "memory", "collective")
