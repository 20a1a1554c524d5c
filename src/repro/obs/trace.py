"""Span tracer: parent-linked wall-time spans with attached metric deltas.

    with obs.span("serve.decode_tick", tick=7):
        ...

Spans form a per-thread stack: a span opened inside another records the
outer span's id as its parent, so an exported trace reconstructs the call
tree (tick -> admit -> prefill, tick -> decode, ...).  On exit each span
carries:

* wall time (`perf_counter` delta),
* user attributes (the keyword args),
* **metric deltas** — the change in every registry *counter* over the
  span's lifetime, nonzero entries only.  A `serve.decode_tick` span thus
  shows exactly how many store fills / hits / bytes that one tick cost,
  without the instrumented layers knowing about each other.

An enabled tracer also opens a `jax.profiler.TraceAnnotation` of the
span's name for its lifetime, so every span lands, under the name it has
in the JSONL log, in any profile being captured: on the clock of the
device trace, beside the device operations it dispatched.  With no
profile capturing, an annotation records nothing and costs about half a
microsecond (measured on a TPU v5e host).

A disabled tracer's `span()` is a shared no-op context manager (one dict
lookup, no allocation, no annotation) — the same off-is-free rule as the
registry.

`profile_dir` arms `jax.profiler` capture: spans entered with
`profile=True` run under `jax.profiler.trace(profile_dir)` (outermost
profiled span only — the profiler is process-global), so
`--profile-dir /tmp/prof` turns a marked span into a full XLA trace you
can open in TensorBoard/Perfetto without touching the call site.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable

from repro.obs.registry import MetricsRegistry


@dataclasses.dataclass
class Span:
    """One finished (or in-flight) span."""

    name: str
    span_id: int
    parent_id: int | None
    t0_s: float                      # process-relative (perf_counter)
    attrs: dict[str, Any]
    dur_s: float | None = None
    metrics: dict[str, float] = dataclasses.field(default_factory=dict)

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def to_event(self) -> dict[str, Any]:
        """The JSONL `span` event (see repro.obs.export.validate_event)."""
        return {
            "kind": "span",
            "name": self.name,
            "id": self.span_id,
            "parent": self.parent_id,
            "t0_s": round(self.t0_s, 6),
            "dur_s": round(self.dur_s or 0.0, 6),
            "attrs": self.attrs,
            "metrics": {k: round(v, 6) for k, v in self.metrics.items()},
        }


class _NullSpan:
    """What a disabled tracer yields: attribute writes vanish."""

    __slots__ = ()

    def set_attr(self, key: str, value: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


@contextlib.contextmanager
def _null_ctx():
    yield _NULL_SPAN


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Per-process tracer over a `MetricsRegistry` (for counter deltas).

    `on_finish` (set by `obs.configure`) streams each finished span to the
    JSONL exporter; finished spans are also kept in a bounded in-memory
    list (`finished`) for reports and tests.
    """

    def __init__(self, registry: MetricsRegistry | None = None, *,
                 enabled: bool = True, max_spans: int = 100_000,
                 profile_dir: str | None = None,
                 on_finish: Callable[[Span], None] | None = None):
        self.enabled = enabled
        self.registry = registry
        self.max_spans = max_spans
        self.profile_dir = profile_dir
        self.on_finish = on_finish
        self.finished: list[Span] = []
        self.dropped = 0
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._profiling = False  # a profiled span is already active

    def _stack(self) -> list[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, *, profile: bool = False, **attrs):
        """Open a span; yields the `Span` (set late attrs on it)."""
        if not self.enabled:
            yield _NULL_SPAN
            return
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        sp = Span(
            name=name, span_id=next(self._ids), parent_id=parent,
            t0_s=time.perf_counter(), attrs=dict(attrs),
        )
        before = (self.registry.counter_values()
                  if self.registry is not None else {})
        stack.append(sp)
        profiler_ctx = contextlib.nullcontext()
        started_profile = False
        if profile and self.profile_dir and not self._profiling:
            try:
                import jax

                profiler_ctx = jax.profiler.trace(self.profile_dir)
                self._profiling = started_profile = True
            except Exception:  # profiler unavailable: span still records
                profiler_ctx = contextlib.nullcontext()
        try:
            # the annotation opens inside a capture this span starts, so
            # that the capture records it
            with profiler_ctx, _annotation(name):
                yield sp
        finally:
            if started_profile:
                self._profiling = False
            stack.pop()
            sp.dur_s = time.perf_counter() - sp.t0_s
            if self.registry is not None:
                after = self.registry.counter_values()
                sp.metrics = {
                    k: after[k] - before.get(k, 0.0)
                    for k in after
                    if after[k] - before.get(k, 0.0) != 0.0
                }
            with self._lock:
                if len(self.finished) < self.max_spans:
                    self.finished.append(sp)
                else:
                    self.dropped += 1
            if self.on_finish is not None:
                self.on_finish(sp)

    def span_count(self) -> int:
        with self._lock:
            return len(self.finished) + self.dropped


class _NullTracer:
    """Disabled tracer: `span()` returns a shared no-op context."""

    enabled = False
    finished: list[Span] = []
    dropped = 0

    def span(self, name: str, **attrs):
        return _null_ctx()

    def span_count(self) -> int:
        return 0


NULL_TRACER = _NullTracer()
