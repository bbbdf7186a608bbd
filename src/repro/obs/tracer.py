"""Host-side span tracer for the streaming-partition pipeline.

Span model
----------
A *span* is a named interval ``[t0, t1]`` on the monotonic clock
(``time.perf_counter``), tagged with a category and structured attrs and
placed on a *track*. Tracks default to the recording thread's name (the
main stepping loop records onto ``main``, the read-ahead worker onto
``adwise-readahead``); callers can override with ``track=`` to create
virtual lanes (restream passes use ``restream-pass-<j>``). Nesting is
by timestamp containment per track — exactly how Perfetto renders
Chrome trace events — so spans carry no explicit parent pointers.

Profiler annotations
--------------------
While a ``jax.profiler`` session records, every span opened with
``span()`` — on a :class:`Tracer` or on the null tracer — is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``: the same span
sites, on the profiler's clock, beside the device's timeline. Its attrs
ride along as the annotation's metadata (``set()`` adds to both). Whether
a span records into the tracer depends only on ``tracer.enabled``.

Two recording paths:

* ``with tracer.span(name, cat=...):`` — a scope. Hot sites whose
  timestamps also feed a stats counter pass the floats themselves:
  ``sp = tracer.span(...).open(t0)`` ... ``sp.close(t1)``. The span then
  shares the *exact* float pair with the counter (the blocking-refill span
  reuses the timestamps behind ``h2d_wait_s``), so category wall totals
  reconcile with the scalar counters bit-for-bit.
* ``tracer.add_span(name, cat, t0, t1)`` — a finished interval, recorded
  into the tracer only: an annotation has to be open while the work runs,
  so this path never reaches the profiler.

Overhead contract
-----------------
Hot paths gate on ``tracer.enabled`` (a plain class attribute — one
attribute load) before building attr dicts. With tracing disabled
callers hold :data:`NULL_TRACER`, a module-level singleton whose
``span()`` costs one ``TraceAnnotation.is_enabled()`` check and, with no
profiler session, returns a shared no-op span object: the disabled path
allocates nothing per call and records nothing, which is what lets the
driver keep a tracer on its hottest loops unconditionally.

Everything here is host-side: spans must wrap dispatch and host waits
only — never values still on device. Calling the tracer *inside* a
jit-traced step closure would concretize tracers and add a per-step host
sync; ``tools/staticcheck`` rule SC003 flags exactly that (see
``tools/staticcheck/README.md``). Device phases are named inside the
programs with ``jax.named_scope`` instead.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "resolve_tracer",
    "TraceSummary",
    "SpanRecord",
]


class SpanRecord(NamedTuple):
    """One recorded interval. ``t0``/``t1`` are perf_counter seconds."""

    name: str
    cat: str
    track: str
    thread: str
    t0: float
    t1: float
    attrs: Dict[str, Any]


class InstantRecord(NamedTuple):
    name: str
    cat: str
    track: str
    thread: str
    t: float
    attrs: Dict[str, Any]


class CounterRecord(NamedTuple):
    name: str
    track: str
    t: float
    value: float


class TraceSummary(NamedTuple):
    """Per-category wall totals over a tracer's recorded spans.

    ``categories`` maps category -> ``{"count": n, "wall_s": total}``;
    the totals are sums of span durations (concurrent spans in one
    category double-count, by design — they reconcile with the *scalar*
    counters, which accumulate the same way: the ``refill`` category
    total equals ``h2d_wait_s``, the ``stage`` total equals
    ``prestage_wall_s``, and the ``scan`` count equals ``scan_calls``).
    """

    events: int
    wall_s: float
    categories: Dict[str, Dict[str, float]]
    tracks: Tuple[str, ...]

    def as_dict(self) -> Dict[str, Any]:
        return {
            "events": self.events,
            "wall_s": self.wall_s,
            "categories": self.categories,
            "tracks": list(self.tracks),
        }


class _Span:
    """A span scope: ``with`` it, or ``open()``/``close()`` it with
    caller-taken timestamps. While a profiler session records it is also
    the annotation ``repro.<name>``; it records into its tracer on close
    when the tracer is enabled. ``t0``/``t1`` hold its interval."""

    __slots__ = ("_tracer", "_name", "_cat", "_track", "_attrs", "_note",
                 "t0", "t1")

    def __init__(
        self,
        tracer: Any,
        name: str,
        cat: str,
        track: Optional[str],
        attrs: Dict[str, Any],
    ) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._track = track
        self._attrs = attrs
        self._note: Optional[TraceAnnotation] = None
        self.t0 = 0.0
        self.t1 = 0.0

    def set(self, **attrs: Any) -> "_Span":
        """Attach attrs discovered mid-span (e.g. per-pass quality)."""
        self._attrs.update(attrs)
        if self._note is not None:
            self._note.set_metadata(**attrs)
        return self

    def open(self, t0: Optional[float] = None) -> "_Span":
        if TraceAnnotation.is_enabled():
            self._note = TraceAnnotation("repro." + self._name, **self._attrs)
            self._note.__enter__()
        self.t0 = time.perf_counter() if t0 is None else t0
        return self

    def close(self, t1: Optional[float] = None) -> None:
        self.t1 = time.perf_counter() if t1 is None else t1
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        if self._tracer.enabled:
            self._tracer.add_span(
                self._name, self._cat, self.t0, self.t1,
                track=self._track, attrs=self._attrs,
            )

    def __enter__(self) -> "_Span":
        return self.open()

    def __exit__(self, *exc: Any) -> None:
        self.close()


class _NullSpan:
    """Shared no-op span: zero allocation on the disabled path."""

    __slots__ = ()
    t0 = 0.0
    t1 = 0.0

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def open(self, t0: Optional[float] = None) -> "_NullSpan":
        return self

    def close(self, t1: Optional[float] = None) -> None:
        return None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects spans/instants/counters; thread-safe; export-ready.

    The epoch ``t0`` is taken at construction; exported timestamps are
    relative to it. All recording methods may be called from any thread.
    """

    enabled: bool = True

    def __init__(self) -> None:
        self.t0 = time.perf_counter()
        self._lock = threading.Lock()
        self.spans: List[SpanRecord] = []
        self.instants: List[InstantRecord] = []
        self.counters: List[CounterRecord] = []

    # -- recording ---------------------------------------------------------
    def _track(self, track: Optional[str]) -> str:
        if track is not None:
            return track
        name = threading.current_thread().name
        return "main" if name == "MainThread" else name

    def span(
        self, name: str, cat: str = "misc", track: Optional[str] = None, **attrs: Any
    ) -> _Span:
        """A span scope (see :class:`_Span`)."""
        return _Span(self, name, cat, track, attrs)

    def add_span(
        self,
        name: str,
        cat: str,
        t0: float,
        t1: float,
        track: Optional[str] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record a finished interval with caller-taken timestamps (into
        this tracer only; see the module docstring)."""
        rec = SpanRecord(
            name,
            cat,
            self._track(track),
            threading.current_thread().name,
            t0,
            t1,
            attrs if attrs is not None else {},
        )
        with self._lock:
            self.spans.append(rec)

    def instant(
        self, name: str, cat: str = "misc", track: Optional[str] = None, **attrs: Any
    ) -> None:
        rec = InstantRecord(
            name,
            cat,
            self._track(track),
            threading.current_thread().name,
            time.perf_counter(),
            attrs,
        )
        with self._lock:
            self.instants.append(rec)

    def gauge(self, name: str, value: float, track: Optional[str] = None) -> None:
        rec = CounterRecord(name, self._track(track), time.perf_counter(), float(value))
        with self._lock:
            self.counters.append(rec)

    # -- reading -----------------------------------------------------------
    def summary(self) -> TraceSummary:
        """Per-category totals over everything recorded so far.

        Cumulative over the tracer's lifetime: a tracer threaded through
        several restream passes summarizes all of them.
        """
        with self._lock:
            spans = list(self.spans)
            n_other = len(self.instants) + len(self.counters)
        cats: Dict[str, Dict[str, float]] = {}
        tracks: Dict[str, None] = {}
        lo, hi = float("inf"), float("-inf")
        for s in spans:
            c = cats.setdefault(s.cat, {"count": 0, "wall_s": 0.0})
            c["count"] += 1
            c["wall_s"] += s.t1 - s.t0
            tracks.setdefault(s.track)
            lo, hi = min(lo, s.t0), max(hi, s.t1)
        return TraceSummary(
            events=len(spans) + n_other,
            wall_s=(hi - lo) if spans else 0.0,
            categories=cats,
            tracks=tuple(tracks),
        )

    def export(self, path: str) -> int:
        """Write a Chrome trace-event JSON; returns the event count."""
        from .export import export_chrome_trace

        return export_chrome_trace(self, path)


class NullTracer:
    """API-compatible no-op. ``enabled`` is False; hot paths branch on it
    and skip building attrs; ``span()`` gets a shared no-op span object, so
    the disabled path allocates nothing per call. While a profiler session
    records, ``span()`` still opens the ``repro.<name>`` annotation."""

    __slots__ = ()
    enabled: bool = False
    t0: float = 0.0

    def span(
        self, name: str, cat: str = "misc", track: Optional[str] = None, **attrs: Any
    ) -> Any:
        if TraceAnnotation.is_enabled():
            return _Span(self, name, cat, track, attrs)
        return _NULL_SPAN

    def add_span(
        self,
        name: str,
        cat: str,
        t0: float,
        t1: float,
        track: Optional[str] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        return None

    def instant(
        self, name: str, cat: str = "misc", track: Optional[str] = None, **attrs: Any
    ) -> None:
        return None

    def gauge(self, name: str, value: float, track: Optional[str] = None) -> None:
        return None

    def summary(self) -> TraceSummary:
        return TraceSummary(events=0, wall_s=0.0, categories={}, tracks=())

    def export(self, path: str) -> int:
        raise RuntimeError("cannot export from a NullTracer (tracing is disabled)")


NULL_TRACER = NullTracer()


def resolve_tracer(trace: Any) -> Any:
    """``None`` -> the module-level null singleton; anything else passes
    through. The single entry point every ``trace=`` kwarg funnels into."""
    return NULL_TRACER if trace is None else trace
