"""Guarded execution: the degradation lattice behind every ``ops.*`` call.

:func:`run` takes an ordered list of execution *levels* — for a windowed
op typically ``tuned → default → alternate strategy/backend → reference
oracle`` — and serves the result of the first level that succeeds.  What
"succeeds" means, and what happens when nothing does, is set by the
failure policy (``repro.config.on_failure``):

- ``'fallback'`` (opt-in, by name): a failing level demotes to
  the next one.  Every demotion bumps the ``robust.demotion`` counter
  (label ``op:from->to``) and annotates the open trace span, so
  degradations are observable, never silent.  If every level fails, the
  last *real* error re-raises unchanged (an injected fault or numerics
  trip with no surviving level raises :class:`GuardedExecutionError`).
- ``'raise'`` (the default, also pinned in tests/conftest.py): an
  injected fault or numerics trip surfaces immediately as a structured
  :class:`GuardedExecutionError` naming the site; any *other* exception
  re-raises completely unchanged, so pre-existing validation errors
  (``ops.stencil: ...`` ValueErrors etc.) keep their types and messages.

The opt-in numerics guard (``repro.config.check_numerics``) treats a
non-finite concrete output as a level failure under the same policy.
Outputs that are still tracers (a guarded op called inside a user
``jax.jit``) are skipped — trace-time values carry no numerics.

Ordering rationale for the lattice lives in DESIGN.md §16.3: each step
down gives up performance before it gives up the engine, and gives up
the engine before it gives up the answer.  The final level is always a
pure-XLA reference oracle, which shares no lowering code with the
engine, so a lowering bug cannot take out its own fallback.

Overhead discipline: with no failure, :func:`run` is one ``try`` around
the primary thunk — no policy read, no config import, no allocation
beyond the level list the caller built.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Sequence

from repro import obs
from repro.robust import faults


# ---------------------------------------------------------------------------
# Structured errors
# ---------------------------------------------------------------------------


class GuardedExecutionError(RuntimeError):
    """A guarded op failed (or was configured to surface a failure).

    ``op`` is the guarded surface (e.g. ``"stencil"``), ``failures`` the
    ``(level, exception)`` chain that was attempted, ``site`` the first
    injection site implicated (``None`` for organic failures).
    """

    def __init__(self, op: str, failures: Sequence[tuple[str, Exception]]):
        self.op = op
        self.failures = list(failures)
        self.site = next(
            (e.site for _, e in self.failures if isinstance(e, faults.FaultInjected)),
            None,
        )
        chain = "; ".join(
            f"level '{lvl}': {type(e).__name__}: {e}" for lvl, e in self.failures
        )
        at = f" at site '{self.site}'" if self.site else ""
        super().__init__(f"guarded op '{op}' failed{at} ({chain})")


class NumericsError(RuntimeError):
    """A guarded level produced non-finite output (REPRO_CHECK_NUMERICS)."""

    def __init__(self, op: str, level: str):
        super().__init__(
            f"guarded op '{op}' level '{level}' produced non-finite output"
        )
        self.op = op
        self.level = level


class MeasurementError(RuntimeError):
    """A tuner candidate measurement was unusable — non-finite/negative
    median or non-finite kernel output (site ``tuning.measure``)."""


class SidecarError(RuntimeError):
    """A tuning-sidecar load/save failed under ``on_failure='raise'``
    (sites ``tuning.sidecar.load`` / ``tuning.sidecar.save``)."""


# ---------------------------------------------------------------------------
# Policy accessors — lazy config import (config pulls in models.base; the
# guard must stay importable from anywhere in core/ without cycles).
# ---------------------------------------------------------------------------


def on_failure() -> str:
    from repro import config

    return config.on_failure()


def set_on_failure(mode: str | None) -> None:
    from repro import config

    config.set_on_failure(mode)


@contextlib.contextmanager
def failure_policy(mode: str):
    """``with failure_policy('raise'): ...`` — scoped policy override."""
    from repro import config

    prev = config._ON_FAILURE
    config.set_on_failure(mode)
    try:
        yield
    finally:
        config._ON_FAILURE = prev


@contextlib.contextmanager
def checking_numerics(flag: bool = True):
    """Scoped override of the non-finite output guard."""
    from repro import config

    prev = config._CHECK_NUMERICS
    config.set_check_numerics(flag)
    try:
        yield
    finally:
        config._CHECK_NUMERICS = prev


def _numerics_on() -> bool:
    from repro import config

    return config.check_numerics()


def has_nonfinite(out: Any) -> bool:
    """True if any concrete inexact leaf of *out* contains NaN/Inf.

    Tracer leaves (inside jit tracing) are skipped — they carry no
    values, and aborting a trace on their account would poison the
    cache with a spurious failure.
    """
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(out):
        if isinstance(leaf, jax.core.Tracer):
            continue
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.inexact):
            continue
        if not bool(jnp.isfinite(leaf).all()):
            return True
    return False


# ---------------------------------------------------------------------------
# The guarded dispatcher
# ---------------------------------------------------------------------------

_SYNTHETIC = (faults.FaultInjected, NumericsError)


def run(op: str, levels: Sequence[tuple[str, Callable[[], Any]]]) -> Any:
    """Execute *levels* in order, serving the first success (see module doc).

    *levels* is ``[(name, thunk), ...]`` ordered from the preferred
    execution to the oracle of last resort.  Thunks must be
    self-contained closures: re-invoking a later level never depends on
    state a failed earlier level half-mutated.
    """
    failures: list[tuple[str, Exception]] = []
    n = len(levels)
    check_num = _numerics_on()
    for i, (name, thunk) in enumerate(levels):
        err: Exception | None = None
        try:
            out = thunk()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — the guard's whole job
            err = e
        if err is None and check_num and has_nonfinite(out):
            err = NumericsError(op, name)
            obs.metrics.inc("robust.nonfinite", op)
        if err is None:
            if i:
                obs.metrics.inc("robust.served_degraded", f"{op}:{name}")
            return out
        failures.append((name, err))
        if on_failure() == "raise":
            if isinstance(err, _SYNTHETIC):
                raise GuardedExecutionError(op, failures) from err
            raise err
        if i + 1 < n:
            nxt = levels[i + 1][0]
            obs.metrics.inc("robust.demotion", f"{op}:{name}->{nxt}")
            obs.trace.annotate(demoted=f"{name}->{nxt}",
                               cause=type(err).__name__)
            continue
        # Lattice exhausted. Surface the most informative error: the
        # last organic exception if any level failed for real, else the
        # structured summary of the injected/numerics chain.
        real = [e for _, e in failures if not isinstance(e, _SYNTHETIC)]
        obs.metrics.inc("robust.exhausted", op)
        if real:
            raise real[-1]
        raise GuardedExecutionError(op, failures) from err
    raise ValueError(f"guarded op '{op}' was given no execution levels")
