"""Nestable wall-time spans -> ``span_ms{name=...}`` histograms.

``span("index_rebuild", mode="full")`` times its body into the default
registry's ``span_ms`` histogram under the given name/labels.  Spans
nest freely (each ``with`` creates an independent timing — no implicit
parent/child naming) and are reentrant across threads: the serving
tier's background rebuild thread and the request loop time concurrently
into their own series without interference (per-series locks).

When a JAX profiler trace is being captured, spans additionally forward
to ``jax.profiler.TraceAnnotation`` so the same names show up on the
host timeline of the trace viewer next to the XLA device lanes, with the
labels (and any ``trace_args``, which stay out of the registry) as the
event's stats.  The forwarding is auto-detected per span entry (cheap:
one attribute read) and can be forced on/off with
``set_trace_annotations``.

``counts("train_window", steps=20, encoded=5120)`` adds each value to the
counter ``<name>_<key>_total`` and, while a trace is being captured,
writes one zero-work ``TraceAnnotation`` carrying the values, so that
counts made for a stretch of work sit at that moment on the trace's clock
(a reader sums the ones inside its window).
"""
from __future__ import annotations

import time

from . import _default

# tri-state: None = auto (forward only while a profiler session is
# active), True/False = forced
_trace_mode = None
_jprof_state = False      # False = not yet resolved; None = unavailable


def set_trace_annotations(mode):
    """``True``/``False`` force TraceAnnotation forwarding; ``None``
    restores auto-detection."""
    global _trace_mode
    _trace_mode = mode


def _profiling_active() -> bool:
    global _jprof_state
    if _trace_mode is not None:
        return _trace_mode
    if _jprof_state is False:      # resolve the state object exactly once
        try:
            from jax._src import profiler as _jprof
            _jprof_state = _jprof._profile_state
        except Exception:
            _jprof_state = None
    if _jprof_state is None:
        return False
    return _jprof_state.profile_session is not None


class span:
    """Context manager timing its body into ``span_ms{name=..., labels}``.

    One instance per ``with`` statement (the normal idiom); a kept
    instance may be re-entered sequentially but not concurrently with
    itself — create per use for concurrent timing.
    """

    __slots__ = ("_hist", "_name", "_args", "_t0", "_ta")

    def __init__(self, name: str, *, registry=None, trace_args=None,
                 **labels):
        """``labels`` key the ``span_ms`` series and go to the trace;
        ``trace_args`` (per-call values such as a batch's user count)
        go to the trace only, so they add no series."""
        reg = registry if registry is not None else _default.registry()
        self._name = name
        self._hist = reg.histogram("span_ms", name=name, **labels) \
            if reg.enabled else None
        self._args = {**labels, **trace_args} if trace_args else labels
        self._ta = None

    def __enter__(self):
        if self._hist is None:
            return self
        if _profiling_active():
            try:
                from jax.profiler import TraceAnnotation
                self._ta = TraceAnnotation(self._name, **self._args)
                self._ta.__enter__()
            except Exception:
                self._ta = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._hist is not None:
            self._hist.observe((time.perf_counter() - self._t0) * 1e3)
            if self._ta is not None:
                self._ta.__exit__(*exc)
                self._ta = None
        return False


def counts(name: str, /, *, registry=None, **values):
    """Add each value to the counter ``<name>_<key>_total``; while a
    profiler trace is being captured, also write one ``TraceAnnotation``
    named ``name`` whose stats are the values."""
    reg = registry if registry is not None else _default.registry()
    if not reg.enabled:
        return
    for key, v in values.items():
        reg.counter(f"{name}_{key}_total").inc(v)
    if _profiling_active():
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name, **values):
            pass
