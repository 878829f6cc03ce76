"""Host-side span tracer for the training and serving drivers.

Phase-level wall-clock is the instrument every later perf PR (sharding,
pipelined segments — ROADMAP Open Items 1 and 5) needs: you cannot
overlap segment dispatch with scalar drain until you can SEE how long
each takes. :class:`Tracer` provides nested spans (``compile`` /
``dispatch`` / ``drain`` / ``eval`` in the engine; ``prefill`` /
``decode`` in serving) with microsecond timestamps, point events
(``EngineCache`` hits/misses, SLO summaries), an aggregate
:meth:`Tracer.rollup`, and an optional mirror of every record into a
:class:`repro.obs.JsonlSink` — one JSONL format shared by training and
serving telemetry.

Everything here is host Python around the dispatch boundary: a span
never enters jitted code, so tracing cannot change a compiled program
(and therefore never touches the ``EngineSpec`` cache key).

Timing semantics at the dispatch boundary: JAX dispatch is
asynchronous, so a ``dispatch`` span measures trace+enqueue time while
the following ``drain`` span (which blocks on ``device_get``) absorbs
device compute + transfer. A ``compile`` span wraps the first call of a
segment program, where XLA compilation dominates. Under
``run_experiment(pipeline=True)`` segment ``t+1`` is dispatched before
``t`` is drained, so the device is already working while the host
blocks: ``drain`` shrinks to the RESIDUAL wait (often ~0 once the
pipeline is full) and the sum of ``drain`` spans no longer approximates
device time — compare wall-clock across the ``run`` span instead. A
``compile`` span can also be near-instant when the executable was
deserialized from the persistent compile cache
(:func:`repro.core.cache.use_compile_cache`): the span still marks the first
trace, but XLA loads instead of compiling.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any


class Tracer:
    """Nested span tracer with an optional JSONL sink.

    ``span(name, **attrs)`` is a context manager; spans nest via an
    explicit stack, so every record carries its ``parent`` and
    ``depth``. ``event(name, **attrs)`` records a point event. All
    records are kept in memory (``spans`` / ``events``) and mirrored to
    ``sink`` when one is attached.
    """

    def __init__(self, sink=None, clock=time.perf_counter):
        self.sink = sink
        self.clock = clock
        self.spans: list[dict] = []
        self.events: list[dict] = []
        self._stack: list[str] = []
        self._t0 = clock()

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        t0 = self.clock()
        parent = self._stack[-1] if self._stack else None
        self._stack.append(name)
        try:
            yield self
        finally:
            self._stack.pop()
            rec = {"type": "span", "name": name, "parent": parent,
                   "depth": len(self._stack), "t0_s": t0 - self._t0,
                   "dur_s": self.clock() - t0, **attrs}
            self.spans.append(rec)
            if self.sink is not None:
                self.sink.emit(rec)

    def event(self, name: str, **attrs: Any) -> dict:
        rec = {"type": "event", "name": name,
               "t_s": self.clock() - self._t0, **attrs}
        self.events.append(rec)
        if self.sink is not None:
            self.sink.emit(rec)
        return rec

    def rollup(self) -> dict:
        """Aggregate timing per span name: ``{name: {count, total_s}}``
        plus event counts — the ``RunManifest`` timing payload."""
        out: dict[str, dict] = {}
        for rec in self.spans:
            slot = out.setdefault(rec["name"],
                                  {"count": 0, "total_s": 0.0})
            slot["count"] += 1
            slot["total_s"] += rec["dur_s"]
        ev: dict[str, int] = {}
        for rec in self.events:
            ev[rec["name"]] = ev.get(rec["name"], 0) + 1
        return {"spans": out, "events": ev}


def maybe_profile(profile_dir):
    """Optional ``jax.profiler`` trace hook: a context manager writing a
    device trace under ``profile_dir``, and a no-op when it is unset. A
    profiler that fails raises: a run asked to trace never traces nothing
    in silence."""
    if not profile_dir:
        return contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.trace(str(profile_dir))
