"""Program spans on the profiler's clock, the host-to-device byte counter,
the stats a ``compile`` span states of what it built, and the stage
scopes inside the segment program.

Pins: :func:`repro.obs.trace.span` always enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (with or without a
``Tracer``) and records the same ``Tracer`` span as before when given one;
every driver opens the ``upload`` / ``setup`` / ``finalize`` / ``record``
spans; the ``upload`` span counts the bytes of the host arrays it copies
(0 for arrays already on the device); a real profile holds the
``repro.*`` host events with their stats; and the ``jax.named_scope``
stage names change only metadata, never the compiled segment program.
"""
import contextlib
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facade_paper import lenet, resnet8
from repro.core.bindings import compile_stats, make_binding
from repro.core.cache import CacheEntry, EngineSpec
from repro.core.runner import host_bytes, run_experiment
from repro.data.synthetic import SynthSpec, make_clustered_data
from repro.models.base import get_config
from repro.obs import Obs, Tracer, span

pytestmark = pytest.mark.tier0

CFG = lenet(smoke=True).replace(n_classes=4)
KW = dict(rounds=2, k=2, degree=2, local_steps=2, batch_size=4, lr=0.05,
          eval_every=1, seed=0)
STAGES = ("sample_batches", "topology", "gossip", "select_heads",
          "local_sgd")


@pytest.fixture(scope="module")
def tiny_ds():
    spec = SynthSpec(n_classes=4, image_size=16, samples_per_class=8,
                     test_per_class=8, seed=3)
    return make_clustered_data(spec, cluster_sizes=(3, 1),
                               transforms=("rot0", "rot180"))


@pytest.fixture
def annotations(monkeypatch):
    """Every TraceAnnotation entered, as ("enter"|"exit", name, stats)."""
    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            self.name, self.stats = name, stats

        def __enter__(self):
            seen.append(("enter", self.name, self.stats))

        def __exit__(self, *exc):
            seen.append(("exit", self.name, self.stats))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return seen


# ------------------------------------------------------------- span --
def test_span_annotates_without_a_tracer(annotations):
    with span(None, "upload", bytes=7) as t:
        assert t is None
    assert annotations == [("enter", "repro.upload", {"bytes": 7}),
                           ("exit", "repro.upload", {"bytes": 7})]


def test_span_records_the_tracer_span_it_always_did(annotations):
    ref, got = Tracer(), Tracer()
    with ref.span("drain", length=3):
        with ref.span("inner"):
            pass
    with span(got, "drain", length=3) as t:
        assert t is got
        with span(got, "inner"):
            pass
    strip = [{k: v for k, v in r.items() if k not in ("t0_s", "dur_s")}
             for r in got.spans]
    assert strip == [{k: v for k, v in r.items()
                      if k not in ("t0_s", "dur_s")} for r in ref.spans]
    assert [(e, n) for e, n, _ in annotations] == [
        ("enter", "repro.drain"), ("enter", "repro.inner"),
        ("exit", "repro.inner"), ("exit", "repro.drain")]
    assert annotations[0][2] == {"length": 3}


def test_rollup_totals_the_bytes_spans_carry():
    tr = Tracer()
    for b in (5, 7):
        with tr.span("upload", bytes=b):
            pass
    with tr.span("drain"):
        pass
    roll = tr.rollup()["spans"]
    assert roll["upload"]["count"] == 2 and roll["upload"]["bytes"] == 12
    assert set(roll["drain"]) == {"count", "total_s"}


def test_rollup_counts_what_the_compile_spans_built():
    tr = Tracer()
    for groups in ("1,1,1", "1,1,1", "4,4,4"):
        with tr.span("compile", sgd_path="packed", model="gn-lenet",
                     pack_groups=groups, nodes=32):
            pass
    roll = tr.rollup()["spans"]["compile"]
    assert roll["sgd_path"] == {"packed": 3}
    assert roll["model"] == {"gn-lenet": 3}
    assert roll["pack_groups"] == {"1,1,1": 2, "4,4,4": 1}


@pytest.mark.parametrize("cfg,n,want", [
    (lenet(), 32, {"sgd_path": "packed", "model": "gn-lenet",
                   "pack_groups": "1,1,1"}),
    (lenet(smoke=True), 4, {"sgd_path": "packed", "model": "gn-lenet-smoke",
                            "pack_groups": "4,4,4"}),
    (resnet8(), 32, {"sgd_path": "packed", "model": "resnet8",
                     "pack_groups": "8,8,8,1,1,1,1,1,1"}),
    (get_config("llama3.2-1b", smoke=True), 4,
     {"sgd_path": "vmap", "model": "llama3.2-1b-smoke"}),
], ids=["gn-lenet", "gn-lenet-smoke", "resnet8", "llama"])
def test_compile_stats_name_the_model_and_its_pack_groups(cfg, n, want):
    """GN-LeNet's convolutions are 32 and 64 channels wide, so each keeps
    one node to a group; ResNet8's 16-channel stem and block1 take eight."""
    assert compile_stats(make_binding(cfg), n) == want


# ------------------------------------------------- the drivers' spans --
@pytest.mark.parametrize("driver", ["engine", "checkpointed", "legacy"])
def test_every_driver_opens_the_program_spans(driver, tiny_ds, annotations,
                                              tmp_path):
    obs = Obs(None, health=None)
    ckpt = str(tmp_path / "run.npz") if driver == "checkpointed" else None
    run_experiment("facade", CFG, tiny_ds, obs=obs,
                   engine=driver != "legacy", ckpt=ckpt, **KW)
    roll = obs.tracer.rollup()["spans"]
    want = {"run", "upload", "setup", "finalize", "eval", "cache.entry"}
    if driver != "legacy":
        want |= {"record", "compile", "drain"}
    if ckpt is not None:
        want.add("ckpt.save")
    assert want <= set(roll), sorted(roll)
    assert roll["finalize"]["count"] == 1
    # the profiler sees the same spans, under repro.<name>
    entered = {n for e, n, _ in annotations if e == "enter"}
    assert {f"repro.{s}" for s in want} <= entered
    ups = [s for e, n, s in annotations
           if e == "enter" and n == "repro.upload"]
    assert tiny_ds.train_x.nbytes + tiny_ds.train_y.nbytes in \
        [u["bytes"] for u in ups]


def test_upload_counts_host_bytes_and_nothing_for_device_arrays(tiny_ds):
    want = tiny_ds.train_x.nbytes + tiny_ds.train_y.nbytes
    assert host_bytes(tiny_ds.train_x, tiny_ds.train_y) == want
    on_device = dataclasses.replace(tiny_ds,
                                    train_x=jnp.asarray(tiny_ds.train_x),
                                    train_y=jnp.asarray(tiny_ds.train_y))
    assert host_bytes(on_device.train_x, on_device.train_y) == 0
    for ds, b in ((tiny_ds, want), (on_device, 0)):
        obs = Obs(None, health=None)
        run_experiment("el", CFG, ds, obs=obs, **KW)
        assert obs.tracer.rollup()["spans"]["upload"]["bytes"] == b
        assert obs.manifests[-1].timing["spans"]["upload"]["bytes"] == b


def test_a_profile_holds_the_program_spans_and_their_stats(tiny_ds,
                                                           tmp_path):
    from jax.profiler import ProfileData

    run_experiment("facade", CFG, tiny_ds,
                   obs=Obs(None, health=None, profile_dir=tmp_path), **KW)
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    host = [e for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU" for ln in p.lines for e in ln.events]
    names = {e.name for e in host}
    for s in ("run", "upload", "setup", "compile", "drain", "finalize",
              "eval", "record"):
        assert f"repro.{s}" in names, s
    ups = [dict(e.stats) for e in host if e.name == "repro.upload"]
    assert {"bytes": tiny_ds.train_x.nbytes + tiny_ds.train_y.nbytes} \
        .items() <= max(ups, key=lambda u: u["bytes"]).items()


# ------------------------------------------------------ stage scopes --
_META = re.compile(r",?\s*metadata=\{[^}]*\}")
_DEBUG = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def _program(text: str) -> str:
    """Compiled HLO text without source metadata (op names, locations)."""
    out, skip = [], False
    for line in text.splitlines():
        if line in _DEBUG:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(_META.sub("", line))
    return "\n".join(out)


def _segment_hlo(algo, ds) -> str:
    spec = EngineSpec(algo=algo, cfg=CFG, n=4, k=2, degree=2,
                      local_steps=2, batch_size=4, lr=0.05)
    entry = CacheEntry(spec)
    k_init, k_data = jax.random.split(jax.random.PRNGKey(0))
    carry = entry.engine.init_carry(entry.setup(k_init).state, k_data)
    return entry.engine._build(2, False).lower(
        carry, jnp.asarray(0, jnp.int32), jnp.asarray(ds.train_x),
        jnp.asarray(ds.train_y)).compile().as_text()


def _op_stages(text: str) -> set:
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        for part in path.split("/"):
            core = re.sub(r"^(?:[\w.]+\()*", "", part).rstrip(")")
            if core in STAGES:
                found.add(core)
    return found


@pytest.mark.parametrize("algo", ["facade", "el"])
def test_scopes_never_change_the_compiled_segment(algo, tiny_ds,
                                                  monkeypatch):
    scoped = _segment_hlo(algo, tiny_ds)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _segment_hlo(algo, tiny_ds)
    assert _program(scoped) == _program(plain)
    # ... and the scopes are there to be read, and only in the scoped one
    want = set(STAGES) - ({"select_heads"} if algo == "el" else set())
    assert _op_stages(scoped) == want
    assert _op_stages(plain) == set()
