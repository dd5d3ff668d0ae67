"""Span tracer on the JAX profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: a host event in the
profiler's own trace (``<dir>/plugins/profile/<run>/<host>.xplane.pb``),
on the same clock as the device ops, nested by the profiler under the
span open on the thread, with the span's attributes as the event's
stats. This module keeps no clock, event buffer or exporter of its own:
there is one trace system, the profiler's. ``jax.profiler`` is imported
only when a span is made, so importing :mod:`repro.obs` imports no JAX.

Enabling:

* ``with tracing(log_dir):`` turns spans on and records a profiler
  trace into ``log_dir`` (host spans and device ops, one clock);
  ``with tracing():`` turns spans on for a profiler session started
  elsewhere (``jax.profiler.start_trace``, a TensorBoard capture);
* ``REPRO_TRACE=1`` at import: spans on, for a session started
  elsewhere; ``REPRO_TRACE=<dir>``: spans on, and a profiler trace into
  ``<dir>`` that starts with the first span and stops at exit;
* :func:`enable` / :func:`disable` imperatively.

Overhead policy (DESIGN.md §15): when disabled, :func:`span` returns
the shared :data:`NULL` no-op — one function call, one module-global
boolean read, zero allocation, no clock read. Instrumentation sites
that compute *attributes* (plan signatures, model costs) must guard
that work with :func:`enabled` themselves; the tracer cannot un-pay
work done before the call.

A note on jit: spans emitted inside a ``jax.jit``-ed function body run
at **trace time** — once per compilation, not per call. So
``engine.lower`` marks one plan lowering on the host timeline, while
the un-jitted dispatchers (``run_window_plan``/``run_scan_plan``) emit
a span per eager call. What runs on the device is named by the
program itself, not by spans: kernel names and ``jax.named_scope``
layers (:mod:`repro.obs.scopes`).
"""
from __future__ import annotations

import atexit
import functools
import os

TRACE_ENV = "REPRO_TRACE"

_enabled = False
# REPRO_TRACE=<dir>: the profiler session to start with the first span.
_pending_dir: str | None = None


class _NullSpan:
    """Shared do-nothing span: the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _NullSpan()


def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Turn spans on (they reach whichever profiler session is open)."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def _profile_options():
    """Host spans and device ops, without the Python function tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _start_pending_session() -> None:
    global _pending_dir
    log_dir, _pending_dir = _pending_dir, None
    import jax

    jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
    atexit.register(jax.profiler.stop_trace)


def _annotation(name: str, cat: str, attrs: dict):
    if _pending_dir is not None:
        _start_pending_session()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, cat=cat, **attrs)


def span(name: str, cat: str = "repro", **attrs):
    """A span context manager — or the shared no-op when disabled.

    Attribute values are ints, floats or strings (anything else is
    stored as its ``str``).
    """
    if not _enabled:
        return NULL
    return _annotation(name, cat, attrs)


def annotate(**attrs) -> None:
    """Record attributes where the thread is now: a short ``annotate``
    event carrying them, which the profiler nests inside whatever span
    is open (the guarded dispatcher stamps demotions this way). No-op
    when tracing is disabled — one boolean read, as :func:`span`.
    """
    if not _enabled:
        return
    with _annotation("annotate", "repro", attrs):
        pass


def traced(name: str | None = None, cat: str = "repro"):
    """Decorator form of :func:`span` (zero-overhead when disabled)."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            with _annotation(label, cat, {}):
                return fn(*args, **kwargs)

        return wrapper

    return deco


class tracing:
    """``with obs.tracing(log_dir): ...`` — spans on, and a profiler
    trace of the block written under ``log_dir``.

    Without ``log_dir`` only the spans are turned on, for a profiler
    session started elsewhere. Restores the previous enabled state on
    exit, so nested or tested use cannot leak tracing into the rest of
    the process.
    """

    def __init__(self, log_dir: str | os.PathLike | None = None):
        self.log_dir = log_dir
        self._was = False

    def __enter__(self):
        self._was = _enabled
        if self.log_dir is not None:
            import jax

            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=_profile_options())
        enable()
        return self

    def __exit__(self, *exc):
        if not self._was:
            disable()
        if self.log_dir is not None:
            import jax

            jax.profiler.stop_trace()
        return False


_env = os.environ.get(TRACE_ENV, "")
if _env and _env.lower() not in ("0", "false", "off"):
    enable()
    if _env.lower() not in ("1", "true", "on"):
        _pending_dir = _env
