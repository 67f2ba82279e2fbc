"""Thread-safe span tracer: where the time of a MapReduce fit actually goes.

The paper's decomposition only pays off when mapper ingest, device compute and
the per-iteration reduce actually overlap — and the only way to know is to
look. A `Span` is one timed region (`perf_counter` start + duration) on one
*lane*; lanes map 1:1 onto the threads doing the work (the driver, one
producer per device), so an exported trace renders in Perfetto with one row
per producer and the ingest-bound-vs-compute-bound question answers itself.

Each span has an `id` and the `id` of its `parent`: the span that was open on
the same thread when it was entered (a thread-local stack), so a layer's self
time is its duration less its children's. While it is open a span also holds
a `jax.profiler.TraceAnnotation` of its name, so under `jax.profiler` every
span lands on the host plane of the profile, on the device trace's clock,
beside the ops it caused.

Enabling also starts two process-level instruments (`ProcessWatch`): a
`gc.callbacks` hook that records each collection as a `gc.collect` span and
adds its time to the `process.gc_pause_s` counter, and a watchdog thread on
the `watchdog` lane that sleeps `WATCHDOG_PERIOD_S` at a time and records a
`process.stall` span whenever it wakes more than `WATCHDOG_LATE_S` late: a
ready Python thread waited that long to run, for the interpreter lock held by
another thread, for the collector, or for the OS. Which of these it was, the
watchdog cannot tell; under `jax.profiler` the host plane can (a pause of the
whole process stops the runtime's own threads too). Disabling stops both.

Disabled (the default) the tracer is near-free and allocation-free:
`span(...)` returns a module-level singleton whose __enter__/__exit__ are
empty — no object is created, no clock is read, no lock is taken, no
annotation is entered. Enabled, a span costs two `perf_counter` reads, one
annotation and one list append.

Usage:

    from repro import obs
    obs.enable_tracing()
    with obs.span("pass.map_reduce", cat="pass", blocks=8):
        ...
    obs.write_trace("fit.trace.json")      # Chrome trace-event -> Perfetto
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation

from repro.obs.metrics import counter

#: the watchdog's sleep, and how late a wake-up must be to count as a stall
WATCHDOG_PERIOD_S = 0.005
WATCHDOG_LATE_S = 0.020


class _NullSpan:
    """The disabled path: a shared, stateless, no-op context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One timed region on one lane. Finalized (recorded) on __exit__."""

    __slots__ = ("name", "cat", "lane", "t0", "dur", "attrs", "id", "parent",
                 "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, lane: str,
                 attrs: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.lane = lane
        self.attrs = attrs
        self.id = next(tracer._ids)
        self.parent: int | None = None
        self.t0 = 0.0
        self.dur = 0.0
        self._ann = None

    def set(self, **attrs):
        """Attach/overwrite attributes mid-span (e.g. an iteration's inertia,
        known only after the reduce)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = self._tracer._stack()
        if stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        self._ann = None
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self)
        return False


class Tracer:
    """A span collector. One process-wide instance (`TRACER`) backs the
    module-level API; tests may build their own for isolation."""

    def __init__(self):
        self.enabled = False
        # appended to and copied whole by single list operations, which are
        # atomic: no lock, so the gc hook may record from inside any
        # allocation of any thread
        self._spans: list[Span] = []
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._watch: ProcessWatch | None = None
        # Anchor: wall-clock epoch corresponding to perf_counter() == 0, so
        # exported timestamps are absolute (and comparable across processes).
        self._epoch = time.time() - time.perf_counter()

    # ----------------------------------------------------------- lifecycle

    def enable(self) -> None:
        """Record spans, and watch the process (gc hook and watchdog)."""
        if self._watch is None:
            self._watch = ProcessWatch(self)
            self._watch.start()
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False
        if self._watch is not None:
            self._watch.stop()
            self._watch = None

    def clear(self) -> None:
        self._spans.clear()

    # --------------------------------------------------------------- lanes

    def set_lane(self, lane: str) -> None:
        """Name the calling thread's lane (producers call this once at thread
        start; the driver defaults to "main")."""
        self._local.lane = lane

    def current_lane(self) -> str:
        lane = getattr(self._local, "lane", None)
        if lane is not None:
            return lane
        t = threading.current_thread()
        return "main" if t is threading.main_thread() else t.name

    def _stack(self) -> list:
        """The calling thread's open spans, innermost last."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    # --------------------------------------------------------------- spans

    def span(self, name: str, *, cat: str = "span", lane: str | None = None,
             **attrs: Any):
        """Context manager timing one region. Near-free when disabled: the
        shared NULL_SPAN is returned without touching a clock or a lock."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, cat, lane or self.current_lane(), attrs)

    def record(self, name: str, t0: float, dur: float, *, cat: str,
               lane: str | None = None, **attrs: Any) -> None:
        """Record a span known only after the fact (`t0` on the
        `perf_counter` clock). Its parent is the span open on the calling
        thread; it holds no annotation, as its time has passed."""
        if not self.enabled:
            return
        s = Span(self, name, cat, lane or self.current_lane(), attrs)
        stack = self._stack()
        if stack:
            s.parent = stack[-1].id
        s.t0, s.dur = t0, dur
        self._record(s)

    def _record(self, span: Span) -> None:
        self._spans.append(span)

    def spans(self) -> list[Span]:
        """Snapshot of the recorded spans (record order)."""
        return list(self._spans)

    def lanes(self) -> list[str]:
        """Distinct lanes touched by recorded spans, first-seen order."""
        seen: dict[str, None] = {}
        for s in self.spans():
            seen.setdefault(s.lane, None)
        return list(seen)


class ProcessWatch:
    """The process-level instruments of an enabled tracer: a `gc.callbacks`
    hook (one `gc.collect` span per collection, on the lane of the thread it
    interrupted, and the `process.gc_pause_s` counter) and a watchdog
    thread (one `process.stall` span per late wake-up, from the time it was
    due: the thread waited for the interpreter lock, the collector or the
    OS; `process.watchdog_wakes` counts its wake-ups, so a reader can tell
    "no stall" from "not watched"). `clock` and `sleep` are injectable so a
    test can make a late wake-up without a real stall."""

    def __init__(self, tracer: Tracer, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], Any] | None = None):
        self.tracer = tracer
        self.clock = clock
        self._halt = threading.Event()
        self.sleep = sleep if sleep is not None else self._halt.wait
        self._gc_span: Span | None = None
        self._gc_pause = counter("process.gc_pause_s")
        self._wakes = counter("process.watchdog_wakes")
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self.run, name="obs-watchdog",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._halt.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _on_gc(self, phase: str, info: dict) -> None:
        # One collection runs at a time, so one slot holds the open span.
        if phase == "start":
            if self.tracer.enabled:
                self._gc_span = Span(self.tracer, "gc.collect", "process",
                                     self.tracer.current_lane(),
                                     {"generation": info["generation"]})
                self._gc_span.__enter__()
        elif self._gc_span is not None:
            s, self._gc_span = self._gc_span, None
            s.attrs["collected"] = info["collected"]
            s.__exit__(None, None, None)
            self._gc_pause.inc(s.dur)

    def run(self) -> None:
        """The watchdog loop, until `stop()`."""
        due = self.clock() + WATCHDOG_PERIOD_S
        while not self._halt.is_set():
            self.sleep(max(0.0, due - self.clock()))
            now = self.clock()
            self._wakes.inc()
            if now - due > WATCHDOG_LATE_S:
                self.tracer.record("process.stall", due, now - due,
                                   cat="process", lane="watchdog",
                                   late_ms=(now - due) * 1e3)
            due = now + WATCHDOG_PERIOD_S


TRACER = Tracer()

# ---------------------------------------------------- module-level facade


def enable_tracing() -> None:
    TRACER.enable()


def disable_tracing() -> None:
    TRACER.disable()


def tracing_enabled() -> bool:
    return TRACER.enabled


def clear_trace() -> None:
    TRACER.clear()


def set_lane(lane: str) -> None:
    TRACER.set_lane(lane)


def span(name: str, *, cat: str = "span", lane: str | None = None,
         **attrs: Any):
    if not TRACER.enabled:  # the disabled path, without a second call
        return NULL_SPAN
    return TRACER.span(name, cat=cat, lane=lane, **attrs)
