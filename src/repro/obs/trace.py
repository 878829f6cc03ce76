"""Program spans for the training and serving drivers, on the host and on
the profiler's clock.

Phase-level wall-clock is the instrument every perf change needs: you
cannot tell where a round's time goes until you can SEE how long each
phase takes. :class:`Tracer` provides nested spans (``compile`` /
``dispatch`` / ``drain`` / ``eval`` in the engine; ``prefill`` /
``decode`` in serving) with microsecond timestamps, point events
(``EngineCache`` hits/misses, SLO summaries), an aggregate
:meth:`Tracer.rollup`, and an optional mirror of every record into a
:class:`repro.obs.JsonlSink` — one JSONL format shared by training and
serving telemetry.

Every span the drivers open goes through :func:`span`, which always
enters a ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (its
attrs become the event's stats) and, when a :class:`Tracer` is given,
also opens that tracer's span ``name``. With no profiler session a span
costs a few microseconds of host time (about 5 us on a CPU host; a run
opens a few dozen); inside one (``Obs(profile_dir=...)`` or
``jax.profiler.trace``) the spans sit on the same clock as the device's
operations, so an idle gap on the chip can be put down to the host work
that was open over it. The names: ``repro.run`` (one
experiment), ``repro.upload`` (the train arrays' host-to-device copy, stat
``bytes``; also the evaluator's test batches when it is built),
``repro.setup`` (initial state and carry), ``repro.compile`` /
``repro.dispatch`` (a segment's first / later call; ``compile`` states
which local-SGD program it built, stats ``sgd_path`` ``"packed"`` or
``"vmap"``, ``model`` (the config's name), ``nodes`` and, packed,
``pack_groups``: each convolution's nodes to a group, ``"1,1,1"``),
``repro.drain``,
``repro.finalize``, ``repro.eval``, ``repro.record`` (comm log and
cluster history), ``repro.ckpt.save``, ``repro.cache.entry``; serving adds
``repro.prefill`` / ``repro.decode``. Inside the compiled programs the
algorithm's stages carry ``jax.named_scope`` names instead
(``sample_batches``, ``topology``, ``gossip``, ``select_heads``,
``local_sgd``, ``netsim``, ``obs_frame``, ``predict``, via :func:`scope`)
in each operation's ``op_name``; a TPU trace names an operation by its
HLO instruction, whose ``op_name`` the compiled program's text holds.

Everything here is host Python around the dispatch boundary: a span
never enters jitted code, so tracing cannot change a compiled program
(and therefore never touches the ``EngineSpec`` cache key).

Timing semantics at the dispatch boundary: JAX dispatch is
asynchronous, so a ``dispatch`` span measures trace+enqueue time while
the following ``drain`` span (which blocks on ``device_get``) absorbs
device compute + transfer. A ``compile`` span wraps the first call of a
segment program, where XLA compilation dominates. The engine loop
dispatches segment ``t+1`` before ``t`` is drained, so the device is
already working while the host blocks: ``drain`` is the RESIDUAL wait
(often ~0 when host work covers the segment) and the sum of ``drain``
spans does not approximate device time — compare wall-clock across the
``run`` span instead, or read device time off a profile. A
``compile`` span can also be near-instant when the executable was
deserialized from the persistent compile cache
(:func:`repro.core.cache.use_compile_cache`): the span still marks the first
trace, but XLA loads instead of compiling.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Any

import jax


# span stats that say what a span built; ``Tracer.rollup`` counts each value
STATED = ("sgd_path", "model", "pack_groups")


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
        plus event counts — the ``RunManifest`` timing payload. Spans
        that carry a ``bytes`` attr (``upload``) also total it; spans that
        state what they built (``compile``: ``sgd_path``, ``model``,
        ``pack_groups``) count programs per value, ``{"packed": 1}``."""
        out: dict[str, dict] = {}
        for rec in self.spans:
            slot = out.setdefault(rec["name"],
                                  {"count": 0, "total_s": 0.0})
            slot["count"] += 1
            slot["total_s"] += rec["dur_s"]
            if "bytes" in rec:
                slot["bytes"] = slot.get("bytes", 0) + rec["bytes"]
            for stat in STATED:
                if stat in rec:
                    seen = slot.setdefault(stat, {})
                    seen[rec[stat]] = seen.get(rec[stat], 0) + 1
        ev: dict[str, int] = {}
        for rec in self.events:
            ev[rec["name"]] = ev.get(rec["name"], 0) + 1
        return {"spans": out, "events": ev}


@contextlib.contextmanager
def span(tracer: "Tracer | None", name: str, **attrs: Any):
    """One program span: a ``jax.profiler.TraceAnnotation``
    ``repro.<name>`` with ``attrs`` as its stats, always, and the
    ``tracer``'s span ``name`` with the same attrs when a tracer is given.
    Yields the tracer (or ``None``)."""
    with jax.profiler.TraceAnnotation(f"repro.{name}", **attrs):
        if tracer is None:
            yield None
        else:
            with tracer.span(name, **attrs):
                yield tracer


def scope(name: str):
    """Decorator: stage the function under ``jax.named_scope(name)``, so
    every operation it traces carries ``name`` in its ``op_name`` — how a
    device trace tells the algorithm's stages apart inside one compiled
    program. Metadata only: the compiled program is the same without it."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def maybe_profile(profile_dir):
    """Optional ``jax.profiler`` trace hook: a context manager writing a
    device trace under ``profile_dir``, and a no-op when it is unset. A
    profiler that fails raises: a run asked to trace never traces nothing
    in silence."""
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(str(profile_dir))
