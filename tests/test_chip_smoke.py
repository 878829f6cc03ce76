"""CPU rehearsal of ``chip_smoke.py``, and the compile-cache resolver.

The chip smoke itself needs a TPU (``main`` refuses anything else); its
phase function is device-agnostic, so here it runs phase (a) — a cold
then a warm pass through one ``EngineCache`` — at smoke size with the
same checks: finite accuracies in [0, 1], cumulative bytes == rounds x
the nominal count computed from shapes, a flat ``compile_count`` on the
warm pass, and cold == warm.
"""
import json
import os
import pathlib
import subprocess
import sys
import tempfile

import jax
import pytest

from repro.configs.facade_paper import lenet
from repro.core import cache as cache_mod
from repro.core.runner import run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

CFG = lenet(smoke=True).replace(n_classes=4)


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(6, 2),
                               transforms=("rot0", "rot180"))


@pytest.mark.parametrize("algo", ["facade", "el"])
def test_phase_rehearsal_passes_its_checks(tiny_ds, algo):
    rec = chip_smoke.run_phase("a", algo, CFG, tiny_ds, rounds=4,
                               eval_every=2)
    assert rec["algo"] == algo
    assert len(rec["final_acc"]) == 2
    assert 0.0 <= rec["fair_acc"] <= 1.0
    assert rec["round_bytes"] > 0


def test_phase_checks_catch_wrong_bytes_and_diverging_runs(tiny_ds):
    kw = dict(rounds=4, eval_every=2, degree=chip_smoke.DEGREE)
    res = run_experiment("facade", CFG, tiny_ds, **kw)
    per_round = chip_smoke.nominal_round_bytes("facade", CFG, 8,
                                               chip_smoke.DEGREE)
    chip_smoke.check_run(res, rounds=4, per_round=per_round, where="ok")
    with pytest.raises(AssertionError, match="bytes after round 2"):
        chip_smoke.check_run(res, rounds=4, per_round=per_round + 4,
                             where="off by one id")
    other = run_experiment("facade", CFG, tiny_ds, seed=1, **kw)
    with pytest.raises(AssertionError, match="differ"):
        chip_smoke.same_run(res, other, "two seeds")


def test_mesh_phase_rehearsal_on_four_host_devices():
    """The ``--chips 4`` phase at smoke size on four forced host devices
    (own process: the device count must be set before jax starts)."""
    child = f"""
import json, sys
sys.path.insert(0, {str(REPO)!r})
import chip_smoke
from repro.configs.facade_paper import lenet
from repro.data.synthetic import SynthSpec, make_clustered_data
spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=4,
                 test_per_class=8, seed=3)
ds = make_clustered_data(spec, (6, 2), ("rot0", "rot180"))
cfg = lenet(smoke=True).replace(n_classes=4)
print(json.dumps(chip_smoke.mesh_phase(4, cfg, ds, rounds=2, eval_batch=8)))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["chips"] == 4 and rec["acc_maxdiff"] <= chip_smoke.ACC_TOL
    # the carry's node-stacked leaves are row-sharded over all four
    assert any("'node'" in line and "on 4 device(s)" in line
               for line in rec["carry"])
    assert [e["cluster"] for e in rec["evaluator"]] == [0, 1]


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


# ------------------------------------------------ compile-cache resolver --
def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache_mod.compile_cache_dir() == str(tmp_path)
    assert cache_mod.use_compile_cache() == str(tmp_path)
    # JAX's own reading of the variable stands: no code names another
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_unset_is_the_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache_mod.compile_cache_dir()
    assert got == str(REPO / ".jax_cache")
    assert got == cache_mod.compile_cache_dir()
    assert str(os.getpid()) not in got
    assert not got.startswith(tempfile.gettempdir())


def test_use_compile_cache_unset_attaches_the_checkout_dir(monkeypatch,
                                                          tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    target = tmp_path / "checkout" / ".jax_cache"
    monkeypatch.setattr(cache_mod, "CHECKOUT_CACHE_DIR", target)
    try:
        assert cache_mod.use_compile_cache() == str(target)
        assert jax.config.jax_compilation_cache_dir == str(target)
        assert target.is_dir()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        cache_mod.detach_persist_dir()
