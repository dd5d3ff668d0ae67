"""The program's layers on the device trace of a cell.

The program names its layers in the compiled HLO (``jax.named_scope``:
``engine.pad``, ``halo.exchange``, ...; see ``repro.obs.scopes``), but a
trace's op events carry only the instruction's text (``%pad.2 = ...``).
So a reader joins the two on the instruction name: :func:`layer_map`
compiles the cell's timed dispatch program once more, as
``bench/rehearse.py`` lowers it but for the devices the run used (the
compile cache the timed run filled makes this a load), and maps each
instruction to its ``op_name`` path. A program that names no layers
(one older than ``repro.obs.scopes``) gives an empty map, and the
readers then report nothing.
"""
from __future__ import annotations

import re

from bench import harness, trace as tr

_MAPS: dict[str, dict[str, str]] = {}
_INSTRUCTION = re.compile(r"%?([^\s=]+)")


def _scopes():
    try:
        from repro.obs import scopes
    except ImportError:          # a program that names no layers
        return None
    return scopes


def layer_map(cell: harness.Cell, devices=None,
              impl: str = "pallas") -> dict[str, str]:
    """``{instruction name: op_name path}`` of the program a dispatch of
    ``cell`` runs on ``devices`` (default: the cell's first chips),
    cached per cell."""
    if cell.name in _MAPS:
        return _MAPS[cell.name]
    scopes = _scopes()
    if scopes is None:
        return _MAPS.setdefault(cell.name, {})
    import jax

    devices = devices or jax.devices()[:cell.chips]
    driver = harness.driver_module(cell).make(
        cell.config, cell.traffic, seed=0, impl=impl, devices=devices)
    x = jax.ShapeDtypeStruct(tuple(cell.config["domain"]),
                             cell.config["dtype"],
                             sharding=driver._sharding())
    text = jax.jit(driver._program).lower(x).compile().as_text()
    return _MAPS.setdefault(cell.name, scopes.instruction_layers(text))


def layer_of(path: str | None, prefix: str) -> str | None:
    """``repro.obs.scopes.layer_of``: the innermost scope of ``path``
    that starts with ``prefix``."""
    return _scopes().layer_of(path, prefix)


def instruction(op_name: str) -> str:
    """The instruction name an op event of the trace begins with."""
    m = _INSTRUCTION.match(op_name)
    return m[1] if m else op_name


def share(run, pick) -> float | None:
    """Device time of the ops for which ``pick(path, op_class)`` holds,
    over device busy time, summed over the chips, in %. ``path`` is the
    op's ``op_name`` path (None where the map has none), ``op_class`` its
    class in ``bench/trace.py``. None without a trace or where the
    program names no layers."""
    if run.trace is None:
        return None
    paths = layer_map(run.cell)
    scopes = _scopes()
    if scopes is None or not any(
            scopes.layer_of(p, "engine.") for p in paths.values()):
        return None
    busy = run.trace.busy_s() * len(run.trace.devices)
    if busy <= 0:
        return None
    picked = sum(
        tr.total(tr.merge((s, e) for s, e, name, cls in d.ops
                          if pick(paths.get(instruction(name)), cls)))
        for d in run.trace.devices) * 1e-9
    return 100.0 * picked / busy
