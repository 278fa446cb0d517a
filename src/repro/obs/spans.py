"""Nestable host-side spans, written to two sinks: an in-memory
`SpanLog` with Chrome-trace-event export, and the ``jax.profiler`` trace.

The run-event log (`repro.obs.events`) answers *what happened* at each
eval point; spans answer *where the wall-clock went*. A `SpanLog` is a
per-run collector of named, nested host-side intervals — build, the
engine's dispatches and eval assembly on the training side; store
export/save/load, the serve step's put/dispatch/tier read-back and replay
batches on the serving side — written out as Chrome trace-event JSON that
loads directly into Perfetto or ``chrome://tracing``.

Instrumented library code never creates a log itself: it calls the
module-level :func:`span` context manager, which records into whichever
`SpanLog` is *active* (a contextvar set by :meth:`SpanLog.activate`) and
degrades to a near-zero-cost no-op when none is. The outermost caller —
``run_experiment(trace_dir=...)``, ``run_scenario``, the scenarios CLI's
``serve --trace-dir`` — owns the log: it activates one around the whole
operation, so nested layers (scenario build → engine dispatch → store
export → replay batches) all land in a single trace, and saves it next
to the JSONL event log. ``python -m repro.obs report DIR`` joins the
result with events, metrics, and health.

The second sink needs no log: every :func:`span` also enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
session (``jax.profiler.trace``) records the program's spans on the same
clock as the device's operations, and device idle time can be put down
to the host phase it fell in. Without a profiler session the annotation
is one check in C++. :func:`install_gc_spans` adds the collector's own
passes as ``gc.gen0`` / ``gc.gen1`` / ``gc.gen2`` spans; the program's
entry points (``run_experiment``, ``PersonalizedServer``) install it.

Spans carry free-form attributes (``span("dispatch", chunks=8)``) and
the yielded `Span` accepts late ones via :meth:`Span.set` — the engine
stamps ``compiled_cost`` flops/bytes onto its first dispatch span after
XLA's cost analysis runs, so the exported trace shows static cost next
to measured time. Attributes stay in the `SpanLog`: the profiler's
annotation carries the name alone.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import json
import os
import pathlib
import time
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "SpanLog", "current_log", "install_gc_spans", "span"]

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_log", default=None)


@dataclass
class Span:
    """One named host-side interval: begin/duration (seconds, relative to
    the owning log's epoch), nesting depth, and free-form attributes."""
    name: str
    t0: float                       # start, seconds since log epoch
    depth: int = 0                  # nesting level at begin time
    dur: Optional[float] = None     # seconds; None while still open
    attrs: dict = field(default_factory=dict)

    def set(self, **attrs) -> "Span":
        """Attach (or overwrite) attributes; usable after the span closed
        — attrs serialize at export time, so late annotations (e.g. the
        compile span's cost-analysis flops) still land in the trace."""
        self.attrs.update(attrs)
        return self


class SpanLog:
    """Collector for one run's spans, exportable as Chrome trace events.

    Use :meth:`span` directly, or :meth:`activate` the log so library
    code's module-level :func:`span` calls feed it. Spans nest via a
    stack; the export encodes each as a complete ("X") trace event whose
    ``tid`` is the nesting depth, which Perfetto renders as a flame-like
    track per level.
    """

    def __init__(self, meta: Optional[dict] = None):
        """meta: free-form identity recorded in the exported trace's
        ``metadata`` section (run id, scenario name, ...)."""
        self.meta = dict(meta or {})
        self.spans: list = []
        self._stack: list = []
        self._epoch = time.perf_counter()

    def __len__(self):
        return len(self.spans)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record one nested interval; yields the open `Span` so callers
        can :meth:`Span.set` more attributes. Exceptions propagate after
        the span is closed, so aborted phases still show in the trace."""
        sp = Span(name=name, t0=time.perf_counter() - self._epoch,
                  depth=len(self._stack), attrs=dict(attrs))
        self.spans.append(sp)
        self._stack.append(sp)
        try:
            yield sp
        finally:
            sp.dur = (time.perf_counter() - self._epoch) - sp.t0
            self._stack.pop()

    @contextlib.contextmanager
    def activate(self):
        """Make this the process-wide active log for the dynamic extent:
        every module-level :func:`span` call inside records here. One
        owner at a time — activating while another log is active raises,
        enforcing the ownership rule (nested layers contribute spans via
        :func:`span` instead of owning a second log)."""
        if _ACTIVE.get() is not None:
            raise RuntimeError(
                "a SpanLog is already active; nested layers should "
                "record via span(...) instead of activating their own")
        token = _ACTIVE.set(self)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object: ``{"traceEvents": [...],
        "metadata": ...}`` with one complete ("X") event per closed span
        (timestamps/durations in microseconds), loadable by Perfetto and
        ``chrome://tracing``."""
        pid = os.getpid()
        events = []
        for sp in self.spans:
            if sp.dur is None:          # still open — skip, not droppable
                continue
            events.append({
                "name": sp.name, "cat": "repro", "ph": "X",
                "ts": sp.t0 * 1e6, "dur": sp.dur * 1e6,
                "pid": pid, "tid": sp.depth,
                "args": {k: v for k, v in sp.attrs.items()
                         if isinstance(v, (str, int, float, bool,
                                           type(None)))},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metadata": self.meta}

    def save(self, trace_dir, tag: str = "run") -> pathlib.Path:
        """Write the Chrome-trace JSON to
        ``<trace_dir>/spans-<tag>-<pid>.trace.json`` and return the path
        (parent directories are created)."""
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(tag))
        path = pathlib.Path(trace_dir) / \
            f"spans-{safe}-{os.getpid()}.trace.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome(), sort_keys=True))
        return path

    def summary(self) -> dict:
        """Per-name aggregate over closed spans: ``{name: {count,
        total_ms, mean_ms}}`` — what ``obs report`` and ``summarize``
        render."""
        out: dict = {}
        for sp in self.spans:
            if sp.dur is None:
                continue
            agg = out.setdefault(sp.name, {"count": 0, "total_ms": 0.0})
            agg["count"] += 1
            agg["total_ms"] += sp.dur * 1e3
        for agg in out.values():
            agg["mean_ms"] = agg["total_ms"] / agg["count"]
        return out


class _NullSpan:
    """No-op stand-in yielded by :func:`span` when no log is active."""

    def set(self, **attrs):
        """Discard attributes (no log to record them)."""
        return self


_NULL_SPAN = _NullSpan()


def current_log() -> Optional[SpanLog]:
    """The `SpanLog` activated for the current context, or None."""
    return _ACTIVE.get()


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record a span into the active log, if any, and into the profiler's
    trace as ``repro.<name>``.

    The instrumentation seam: library code (engine, sweep, scenario
    builds, the serving store and server, traffic replay) calls this
    unconditionally — two dict lookups and a perf_counter when a log is
    active, one contextvar read when not, and the annotation's check for
    a profiler session either way.
    """
    log = _ACTIVE.get()
    with TraceAnnotation(f"repro.{name}"):
        if log is None:
            yield _NULL_SPAN
        else:
            with log.span(name, **attrs) as sp:
                yield sp


# spans the collector hook has entered and not yet exited: one per
# collection in progress (collections never overlap)
_GC_OPEN: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        cm = span(f"gc.gen{info['generation']}")
        cm.__enter__()
        _GC_OPEN.append(cm)
    elif _GC_OPEN:
        _GC_OPEN.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Record each pass of Python's garbage collector as a span
    ``gc.gen<generation>`` (``gc.callbacks``). Idempotent: the hook is
    installed once per process, whoever calls."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
