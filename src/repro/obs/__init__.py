"""repro.obs — in-scan telemetry, fairness trajectories, run health
and run manifests.

Four layers, composable but independent:

* **device**: :class:`ObsConfig` + :class:`MetricsFrame`
  (:mod:`.frame`) — a fixed pytree of per-round scalars (update/param
  norms, cluster switches, delivered edges, per-tier byte split,
  gossip-staleness histogram, inclusion) computed INSIDE the engine's
  ``lax.scan`` and drained in the segment's existing single bulk
  ``device_get`` — zero extra dispatches, zero extra host syncs;
* **eval**: :class:`EvalFrame` (:mod:`.evalframe`) — one fairness
  observation per real eval (DP, EO, fair/worst-cluster/per-tier
  accuracy, cluster churn), pure host bookkeeping over arrays the
  evaluator already drains — zero extra dispatches, recorded whether
  or not a device ``ObsConfig`` is attached;
* **host**: :func:`span` + :class:`Tracer` (:mod:`.trace`) — nested
  spans around upload / setup / compile / segment dispatch / scalar
  drain / finalize / eval / record, ``EngineCache`` hit/miss events,
  optional ``jax.profiler`` hook. Every span is also a
  ``jax.profiler.TraceAnnotation`` named ``repro.<span>``, whether or not
  an ``Obs`` is attached, so a profile (``Obs(profile_dir=...)`` or
  ``jax.profiler.trace``) shows the program's host work on the device's
  clock; inside the compiled programs the algorithm's stages are
  ``jax.named_scope`` s (``sample_batches``, ``topology``, ``gossip``,
  ``select_heads``, ``local_sgd``, ``netsim``, ``obs_frame``,
  ``predict``) in each operation's ``op_name`` — plus the
  :mod:`.health` rule engine judging both telemetry streams into a
  per-run :class:`HealthReport` verdict, and :mod:`.report` rendering
  manifest + JSONL into markdown/JSON run reports
  (``python -m repro.obs.report``);
* **disk**: :class:`JsonlSink` + :class:`RunManifest` (:mod:`.sink`) —
  one JSONL record format for training AND serving telemetry, plus a
  manifest (config fingerprint, spec key, settings, timing rollup,
  health verdict) written next to results and stamped into every
  ``BENCH_*.json``.

Usage — any algorithm, either driver, any netsim/topo combination::

    from repro.core.runner import run_experiment
    from repro.obs import Obs, ObsConfig

    obs = Obs(ObsConfig(), jsonl="results/obs/run.jsonl",
              out_dir="results/obs")
    res = run_experiment("facade", cfg, ds, rounds=100, obs=obs)
    obs.frames_table()["cluster_switches"]   # per-round settlement curve
    obs.eval_table()["dp"]                   # DP gap over training
    obs.manifests[-1].health["verdict"]      # "ok" | "warn" | "fail"
    obs.tracer.rollup()                      # where the wall-clock went

``obs=None`` (the default) is bit-for-bit the pre-obs path, and an
ENABLED frame never perturbs a trajectory either — telemetry is pure
observation (both pinned in ``tests/test_obs.py`` for all 5 algorithms
on both drivers). Only :class:`ObsConfig` (the device-side frame spec)
is an ``EngineSpec`` cache-key component; host-side eval telemetry,
health rules and sink/profiler settings on :class:`Obs` never fork the
key or recompile anything.
"""
from __future__ import annotations

import pathlib
from typing import Any

import numpy as np

from .evalframe import (EVAL_FIELDS, EVAL_SCALAR_FIELDS,  # noqa: F401
                        EvalFrame, compute_eval_frame, frame_record)
from .evalframe import eval_table as _eval_table
from .frame import (FRAME_FIELDS, MetricsFrame, ObsConfig,  # noqa: F401
                    compute_frame, tiers_of)
from .health import (HealthConfig, HealthContext,  # noqa: F401
                     HealthIssue, HealthReport, worst_verdict)
from .health import evaluate as evaluate_health  # noqa: F401
from .sink import (JsonlSink, RunManifest, bench_stamp,  # noqa: F401
                   fingerprint, read_jsonl)
from .trace import Tracer, maybe_profile, span  # noqa: F401


class Obs:
    """Host-side observability context for one or more runs.

    ``config``: the device-side :class:`ObsConfig` (``None`` = spans and
    manifests only, no in-scan frame — and no cache-key fork);
    ``health``: the :class:`HealthConfig` thresholds the driver judges
    each run against at run end (``None`` = skip health evaluation);
    ``jsonl``/``sink``: where events go (``jsonl`` path builds a
    :class:`JsonlSink`); ``out_dir``: where per-run manifests are
    written; ``profile_dir``: optional ``jax.profiler`` trace directory.

    One ``Obs`` may span many runs (a sweep shares one): frames, eval
    frames and manifests accumulate, with ``run.begin``/``run.end``
    events marking the boundaries in the JSONL stream and
    :meth:`run_frames_table`/:meth:`run_eval_table` slicing out the
    current run.
    """

    def __init__(self, config: "ObsConfig | None" = ObsConfig(), *,
                 health: "HealthConfig | None" = HealthConfig(),
                 jsonl=None, sink=None, out_dir=None, profile_dir=None):
        self.config = config
        self.health_config = health
        self.sink = sink if sink is not None else (
            JsonlSink(jsonl) if jsonl is not None else None)
        self.tracer = Tracer(sink=self.sink)
        self.out_dir = pathlib.Path(out_dir) if out_dir is not None else None
        self.profile_dir = profile_dir
        self.frames: list[tuple] = []      # (rounds [m], MetricsFrame [m,...])
        self.eval_frames: list[EvalFrame] = []
        self.manifests: list[RunManifest] = []
        self._frames_mark = 0              # where the current run's frames
        self._evals_mark = 0               # ... and eval frames begin

    # -- run lifecycle ------------------------------------------------------
    def begin_run(self, **attrs: Any) -> None:
        self._frames_mark = len(self.frames)
        self._evals_mark = len(self.eval_frames)
        self.tracer.event("run.begin", **attrs)

    def end_run(self, manifest: RunManifest) -> RunManifest:
        self.manifests.append(manifest)
        if self.out_dir is not None:
            manifest.save(self.out_dir /
                          f"manifest_{manifest.name}.json")
        self.tracer.event("run.end", run=manifest.name,
                          fingerprint=manifest.fingerprint)
        return manifest

    def profile(self):
        """Context manager: ``jax.profiler`` trace when ``profile_dir``
        is set (a failing profiler raises), else a no-op."""
        return maybe_profile(self.profile_dir)

    # -- frames -------------------------------------------------------------
    def record_frames(self, rounds, frame: MetricsFrame) -> None:
        """Store one drained segment of frames (host numpy, leading axis
        ``len(rounds)``) and mirror a ``metrics`` record to the sink."""
        rounds = np.asarray(rounds, np.int64).reshape(-1)
        frame = MetricsFrame(*(np.asarray(l) for l in frame))
        self.frames.append((rounds, frame))
        if self.sink is not None:
            rec = {"type": "metrics", "rounds": rounds.tolist()}
            for name, leaf in zip(MetricsFrame._fields, frame):
                rec[name] = np.asarray(leaf, np.float64).tolist()
            self.sink.emit(rec)

    def frames_table(self) -> dict:
        """All recorded frames concatenated: ``{"round": [m], field:
        [m, ...]}`` across every run this ``Obs`` observed."""
        return self._frames_table(self.frames)

    def run_frames_table(self) -> dict:
        """Like :meth:`frames_table`, restricted to the run started by
        the most recent :meth:`begin_run` — what health judges."""
        return self._frames_table(self.frames[self._frames_mark:])

    @staticmethod
    def _frames_table(frames) -> dict:
        if not frames:
            return {"round": np.zeros((0,), np.int64),
                    **{f: np.zeros((0,)) for f in MetricsFrame._fields}}
        out = {"round": np.concatenate([r for r, _ in frames])}
        for i, name in enumerate(MetricsFrame._fields):
            out[name] = np.concatenate(
                [np.atleast_1d(f[i]) if f[i].ndim == 0 else f[i]
                 for _, f in frames])
        return out

    # -- eval frames --------------------------------------------------------
    def record_eval(self, frame: EvalFrame) -> None:
        """Store one eval's fairness observation and mirror a
        ``type:"eval"`` record to the sink."""
        self.eval_frames.append(frame)
        if self.sink is not None:
            self.sink.emit(frame_record(frame))

    def eval_table(self) -> dict:
        """All recorded eval frames as aligned columns (numpy for the
        scalar fields, lists for the ragged per-cluster vectors)."""
        return _eval_table(self.eval_frames)

    def run_eval_table(self) -> dict:
        """Like :meth:`eval_table`, restricted to the current run."""
        return _eval_table(self.eval_frames[self._evals_mark:])
