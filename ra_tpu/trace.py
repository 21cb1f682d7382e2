"""Tracing / profiling hooks (SURVEY.md §5 "tracing/profiling").

The reference keeps profiling out of the hot path: a swappable logger in
persistent_term (ra.hrl:206-228) plus commented-out looking_glass flame
hooks in ra_bench (ra_bench.erl:199-212).  This module is the tpu-native
equivalent, with the same always-off-by-default contract:

* :func:`span` — THE span primitive: a ``jax.profiler.TraceAnnotation``,
  so a span lands in the profiler's trace (the xplane) on the thread
  that ran it, beside the device's own timeline, whenever anyone has a
  profiler session open (``jax.profiler.start_trace`` /
  :func:`jax_profile`); with no session it is a read of the
  profiler's own flag and a shared no-op context, and records nothing.
  "On" is "a profiler session is running": there is no other switch;
* :func:`phase_span` — the same span at a boundary that is also a
  ``PhaseStats`` phase: ONE ``with`` feeds both, from one pair of
  clock reads, so an interval is never stamped at two sites;
* a process-wide swappable :class:`Tracer` (``set_tracer`` /
  ``get_tracer``) — the persistent_term '$ra_logger' pattern: where
  one is installed the same spans also go to its bounded in-memory
  buffer, dumped as Chrome trace-event JSON.  It stamps on the
  profiler's clock (``CLOCK_REALTIME``, what the xplane's timestamps
  are taken from), so a Chrome dump and an xplane line up;
* :func:`jax_profile`, wrapping ``jax.profiler.trace`` so a bench run
  can capture an XLA/TPU timeline (the device-side callgrind).

Instrumented sites (``ra.*`` names, registered in
``blackbox.EVENT_REGISTRY``, documented in docs/OBSERVABILITY.md): the
listener's sweep (ra_tpu.wire.server), the ingress pump and settle
(ra_tpu.ingress), the dispatch-ahead driver and the engine's step
dispatch (ra_tpu.engine.lockstep), the durability bridge's shard
workers (ra_tpu.engine.durable), the WAL batch loop (ra_tpu.log.wal),
and anything user code wraps via ``trace.span``.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Iterator, Optional

#: the installed tracer, or None (tracing disabled).  Module attribute on
#: purpose: instrumented call sites read it once per operation.
_tracer: Optional["Tracer"] = None


# -- causal trace context (ISSUE 7) ------------------------------------------
#
# Every classic-path command gets a trace id at ingress (api.py /
# FifoClient / reliable RPC).  Ids are DETERMINISTIC given the run: a
# process-wide counter under a settable origin prefix, so a seeded soak
# replays the same ids (set_trace_origin("soak42")) while the default
# prefix keeps ids unique across cooperating processes.  The context is
# a plain short string — it rides command objects, RPC frames and
# pickles untouched, and flight-recorder events join on it
# (ra_tpu.blackbox / tools/ra_trace.py).

_trace_seq = itertools.count(1)
_trace_origin = f"p{os.getpid()}"


def set_trace_origin(origin: str) -> None:
    """Set the trace-id prefix AND restart the sequence — the knob a
    seeded run uses to make its command trace ids reproducible."""
    global _trace_seq, _trace_origin
    _trace_origin = str(origin)
    _trace_seq = itertools.count(1)


def new_trace_ctx(origin: Optional[str] = None) -> str:
    """Mint one trace context: ``<origin>-<seq>``."""
    return f"{origin or _trace_origin}-{next(_trace_seq)}"


def set_tracer(tracer: Optional["Tracer"]) -> None:
    """Install (or, with None, remove) the process-wide tracer."""
    global _tracer
    _tracer = tracer


def get_tracer() -> Optional["Tracer"]:
    return _tracer


class Tracer:
    """Bounded in-memory span recorder.

    Spans nest freely across threads (thread id becomes the Chrome
    ``tid``); the buffer is a ring of ``capacity`` events — tracing a
    long bench keeps the newest events instead of growing unboundedly.
    """

    def __init__(self, capacity: int = 200_000) -> None:
        self.capacity = capacity
        self._events: list = []
        self._head = 0          # ring cursor once the buffer is full
        self._dropped = 0       # events overwritten after the ring wrapped
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------

    @staticmethod
    def _now_us() -> float:
        """Microseconds on the profiler's clock (``CLOCK_REALTIME``,
        what TraceMe stamps the xplane's events with): a Chrome dump
        and a profiler trace of the same run line up."""
        return time.time_ns() / 1e3

    def _push(self, evt: dict) -> None:
        with self._lock:
            if len(self._events) < self.capacity:
                self._events.append(evt)
            else:
                self._events[self._head] = evt
                self._head = (self._head + 1) % self.capacity
                self._dropped += 1

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "ra", **args: Any) -> Iterator[None]:
        """Record a complete ("ph":"X") span around the with-body."""
        start = self._now_us()
        try:
            yield args      # set_metadata() adds to them while open
        finally:
            self._push({"name": name, "cat": cat, "ph": "X",
                        "ts": start, "dur": self._now_us() - start,
                        "pid": os.getpid(),
                        "tid": threading.get_ident() & 0xFFFF,
                        **({"args": args} if args else {})})

    # -- readout -----------------------------------------------------------

    def events(self) -> list:
        with self._lock:
            if len(self._events) < self.capacity:
                return list(self._events)
            return (self._events[self._head:] + self._events[:self._head])

    @property
    def wrapped(self) -> bool:
        """True once the ring has overwritten at least one event —
        the buffer no longer holds the full history."""
        return self._dropped > 0

    @property
    def dropped_events(self) -> int:
        return self._dropped

    def dump_chrome_trace(self, path: str) -> str:
        """Write the buffer as Chrome trace-event JSON (atomic replace);
        load in chrome://tracing or ui.perfetto.dev."""
        payload = {"traceEvents": self.events(),
                   "displayTimeUnit": "ms"}
        tmp = path + ".partial"
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path

    def summary(self) -> dict:
        """Per-span-name {count, total_us, max_us} rollup — the quick
        console profile when a full timeline is overkill.  The ``_meta``
        entry reports whether the ring wrapped (``wrapped: True`` +
        ``dropped_events``): a truncated trace's counts cover only the
        newest ``capacity`` events and must not be read as totals."""
        out: dict[str, dict] = {}
        for e in self.events():
            if e.get("ph") != "X":
                continue
            s = out.setdefault(e["name"],
                               {"count": 0, "total_us": 0.0, "max_us": 0.0})
            s["count"] += 1
            s["total_us"] += e["dur"]
            s["max_us"] = max(s["max_us"], e["dur"])
        out["_meta"] = {"wrapped": self.wrapped,
                        "dropped_events": self._dropped}
        return out


# -- the span primitive --------------------------------------------------------

#: shared no-op context (nullcontext is documented reentrant+reusable)
#: for a site whose span is conditional, e.g. a wait that did not wait
NULL = contextlib.nullcontext()


def _session_before_jax() -> bool:
    """Importing this module must not import jax (a bench parent that
    touched jax would hold the chip its children need), and a process
    that never imported jax has no profiler session to record into:
    the annotation class is fetched by the first span that runs after
    jax is there."""
    global _annotate, _session_open
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _annotate = TraceAnnotation
    _session_open = TraceAnnotation.is_enabled
    return _session_open()


_annotate = None
#: True while a profiler session runs (TraceMe's own flag, some 0.1 us)
_session_open = _session_before_jax


def active() -> bool:
    """Whether a span would be recorded anywhere: a profiler session
    runs, or a :class:`Tracer` is installed.  For a site whose span
    arguments cost something to compute."""
    return _tracer is not None or _session_open()


class _TracedSpan:
    """A profiler annotation and a Tracer span entered as one."""

    __slots__ = ("_ann", "_rec", "_ann_open", "_args")

    def __init__(self, ann, rec) -> None:
        self._ann, self._rec = ann, rec

    def __enter__(self):
        self._ann_open = self._ann.__enter__()
        self._args = self._rec.__enter__()
        return self

    def __exit__(self, *exc):
        self._rec.__exit__(*exc)
        self._ann.__exit__(*exc)

    def set_metadata(self, **args) -> None:
        if self._ann_open is not None:
            self._ann_open.set_metadata(**args)
        self._args.update(args)


class _PhaseSpan:
    """A span whose interval is also one ``PhaseStats`` sample: the
    annotation and the phase take the same interval from one site.
    ``dt_s`` holds that interval after exit, for a site that also
    keeps a counter of its own.  :meth:`set_metadata` adds arguments
    known only once the span's work has run (a no-op where nothing
    records)."""

    __slots__ = ("_ann", "_stats", "_phase", "_t0", "_open", "dt_s")

    def __init__(self, ann, stats, phase: str) -> None:
        self._ann, self._stats, self._phase = ann, stats, phase

    def __enter__(self):
        self._open = self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def set_metadata(self, **args) -> None:
        if self._open is not None:
            self._open.set_metadata(**args)

    def __exit__(self, *exc):
        self.dt_s = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        if self._stats is not None:
            self._stats.note(self._phase, self.dt_s)


def span(name: str, cat: str = "ra", **args: Any):
    """A span on the profiler's clock: a ``TraceAnnotation`` while a
    profiler session runs (else the shared no-op) and, where a
    :class:`Tracer` is installed, the same interval in its buffer."""
    ann = _annotate(name, **args) if _session_open() else NULL
    t = _tracer
    if t is None:
        return ann
    return _TracedSpan(ann, t.span(name, cat, **args))


def phase_span(name: str, stats, phase: str, cat: str = "ra",
               **args: Any):
    """:func:`span` at a boundary that is also the ``PhaseStats`` phase
    ``phase`` of ``stats``: the one ``with`` notes the phase sample
    (``stats`` None, an owner that wired no accumulator: span only)."""
    return _PhaseSpan(span(name, cat, **args), stats, phase)


# -- device-side profiling ---------------------------------------------------

@contextlib.contextmanager
def jax_profile(log_dir: str) -> Iterator[None]:
    """Capture an XLA profiler trace (TensorBoard/XProf format) around
    the with-body — the device-timeline analogue of the reference's
    looking_glass hooks (ra_bench.erl:199-212).  Requires a live jax
    backend; safe to nest around engine steps.

    The capture is stamped into the flight recorder on exit
    (``profile.captured`` + the profile dir), so a bench-time capture
    shows up in ra_trace timelines next to the events it covers
    instead of being a side file nobody finds (ISSUE 16)."""
    import jax

    from .blackbox import record

    t0 = time.perf_counter()
    with jax.profiler.trace(log_dir):
        yield
    record("profile.captured", dir=str(log_dir),
           wall_s=round(time.perf_counter() - t0, 3))
