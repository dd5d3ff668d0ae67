"""Perf-model-guided autotuner for engine block configurations (§5).

For a given plan + problem shape the tuner enumerates candidate block
configs — ``(block_h, block_w[, block_z], variant)`` for windowed plans,
``(block_r, block_t)`` for scans — prices each with an extension of the
paper's §5 latency model (Eq. 4 compute terms + the §5.3 halo/redundancy
accounting, applied to the *actual* block geometry instead of the warp),
optionally measures the model's top-k candidates with the real kernel,
and caches the winner per (plan, shape, time_steps, backend).

Winners persist: when ``REPRO_TUNING_CACHE`` names a JSON sidecar, every
measured winner is written through to it (keyed by plan signature /
shape / time_steps / backend / context) and the file is loaded on
import, so a warm sidecar makes a cold process perform **zero** tuning
measurements. Shapes never tuned before are *seeded* from the nearest
cached shape of the same plan (log-space distance) instead of retuning —
the engine clamps block configs to the output shape, so a neighbor
shape's winner is always runnable.

Sharding: :func:`shard_tuning_shape` maps a (global shape, mesh
assignment) pair to the halo-extended shard-local shape the engine
actually lowers per device — tune against *that* shape and the winner
stays valid under sharding (the block never exceeds the shard).

Pricing per useful output element (see :func:`model_cost`):

* **compute** — ``t · mads · (T_mad + T_reg)`` plus the shift term
  ``t · shifts · T_shfl`` amortized over the P output rows a roll covers
  (one lane-roll of the whole (P, S) psum block serves all P rows, the
  TPU widening of Eq. 4's per-output (M−1)·T_shfl). ``shift_data``
  halves the effective shift cost: its rolls leave the accumulator
  dependency chain and overlap with FMAs (DESIGN.md §2).
* **memory** — every loaded element costs ``T_gmem/LANES``; the loaded/
  useful ratio is exactly the halo redundancy of §5.3 for the block,
  ``Π(block+t·(ext−1)) / Π(block)``, which temporal blocking widens.

The absolute cycle counts are estimates (the TPU latency row is marked
as such in :mod:`repro.core.perfmodel`); the tuner only consumes the
*ranking*, and the measured pass — which always includes the default
config — guarantees the returned config never loses to the default on
the measured metric.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time
import zlib
from typing import Callable, Sequence

import jax

from repro import obs
from repro.robust import faults as rfaults
from repro.robust import guard as rguard
from repro.robust.guard import MeasurementError, SidecarError

from .perfmodel import (TPU_V5E, HardwareLatencies, machine_for,
                        mxu_tap_rows)
from .plan import SystolicPlan

SIDECAR_ENV = "REPRO_TUNING_CACHE"
MEASURE_REPS_ENV = "REPRO_MEASURE_REPS"
MEASURE_RETRIES_ENV = "REPRO_MEASURE_RETRIES"
TUNE_BUDGET_ENV = "REPRO_TUNE_BUDGET_S"

# A candidate whose IQR exceeds this fraction of its median is a noisy
# sample: re-measure once before letting it into the ranking (§16.4).
OUTLIER_SPREAD_FRACTION = 0.5

# Engine schema version stamped on every sidecar entry. Bump whenever the
# engine's lowering changes what a measured winner *means* (block
# semantics, grid layout, accumulator placement) — stale entries are
# ignored on load and dropped on the next write-through, so a sidecar
# shipped with a checkpoint ages out instead of silently replaying
# configs measured against a different kernel.
#   v1 — PR 1/2 lowering (spatial grids only).
#   v2 — reduction axes: grid gained out/reduce dims + scratch
#        accumulator; NCHW/batched shapes join the key space.
#   v3 — fused pipelines + epilogues + output-strided grids: kernels may
#        carry extra epilogue operands, iterate stage lists and read
#        stride-scaled input tiles.
#   v4 — chunk-streamed scans: scan winners may carry a third block
#        dimension (the chunk length of the streamed schedule), and the
#        scan kernel gained carry-in/-out ports; v3 scan entries priced a
#        different lowering.
#   v5 — lowering strategy: windowed winners carry a ``strategy`` field
#        ('lanes' VPU schedule vs 'mxu' im2row dot_general, DESIGN.md
#        §13) and sidecar keys gain a sixth component (the plan's pinned
#        strategy, or 'auto') so nearest-shape seeding never crosses
#        strategies; v4 entries never tuned over the algorithm choice.
#   v6 — engine backend: sidecar keys gain a seventh component (the
#        engine backend, 'tpu' | 'gpu', DESIGN.md §14) and candidates
#        come from backend-specific grids (warp-multiple pow2 tiles on
#        GPU vs 8×128 sublane/lane tiles on TPU), so a winner measured
#        against one lowering never replays — or seeds — the other;
#        v5 entries never recorded which lowering they measured.
#   v7 — measurement spread: entries carry the ``spread_us`` (IQR across
#        :func:`measure_us` reps) of the winning measurement, so drift
#        analysis (DESIGN.md §15) can tell noisy wins from modeled ones;
#        v6 entries carry medians whose confidence is unknown, and a
#        replayed winner with unknown noise is exactly what the drift
#        monitor exists to rule out.
ENGINE_SCHEMA_VERSION = 7

# VMEM working-set budget per block (f32 elements): input block + psum +
# output must fit comfortably in ~16 MB VMEM; stay conservative.
VMEM_BUDGET_ELEMS = 1 << 20

_WINDOW_BLOCK_H = (8, 16, 32, 64)
_WINDOW_BLOCK_W = (128, 256, 512)
_WINDOW_BLOCK_Z = (4, 8, 16)
_SCAN_BLOCK_R = (8, 16, 32)
_SCAN_BLOCK_T = (128, 256, 512, 1024)
_SCAN_CHUNK_TILES = (1, 2, 4)        # chunk = m × lane tile (streamed scans)

# GPU candidate grids (DESIGN.md §14): warp-multiple pow2 tiles — the
# Triton tile-chooser idiom (BLOCK = next_pow2(n), masked overhang)
# rather than the TPU's 8×128 sublane/lane tiling. Lane tiles are whole
# multiples of the 32-lane warp so every shift_psum hop decomposes into
# intra-warp shuffles + whole-warp hand-offs; row tiles stay small
# because GPU blocks hold 4 warps, not 8 sublanes of a VREG.
_GPU_BLOCK_H = (4, 8, 16, 32)
_GPU_BLOCK_W = (32, 64, 128, 256)
_GPU_BLOCK_Z = (2, 4, 8)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _engine_backend(backend: str | None) -> str:
    """Resolve the tuner's backend argument against the config default."""
    from repro.config import engine_backend, resolve_engine_backend

    return (engine_backend() if backend is None
            else resolve_engine_backend(backend))


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One candidate schedule: output block per windowed axis + variant
    + (since schema v5) the lowering strategy — the tuner's first choice
    between *algorithms* rather than block geometries (DESIGN.md §13).
    ``strategy=None`` means "whatever the plan says" (auto → lanes)."""

    block: tuple[int, ...]          # lane axis last
    variant: str = "shift_psum"
    strategy: str | None = None     # None | 'lanes' | 'mxu'

    def as_kwargs(self, plan: SystolicPlan) -> dict:
        """Render into the kwargs the thin kernel wrappers accept."""
        if plan.combine != "fma":
            kw = {"block_r": self.block[0], "block_t": self.block[1]}
            if len(self.block) == 3:        # chunk-streamed scan (§12)
                kw["chunk"] = self.block[2]
            return kw
        if plan.kind == "conv1d":
            kw = {"block_t": self.block[0], "block_d": self.block[1]}
            if self.strategy is not None:
                kw["strategy"] = self.strategy
            return kw
        kw = {"block_h": self.block[-2], "block_w": self.block[-1]}
        if plan.ndim_spatial == 3:
            kw["block_z"] = self.block[0]
        if plan.M > 1:
            kw["variant"] = self.variant
        if self.strategy is not None:
            kw["strategy"] = self.strategy
        return kw


@dataclasses.dataclass(frozen=True)
class TuneResult:
    config: KernelConfig
    model_cost: float               # est. cycles per useful output
    measured_us: float | None       # None when model-only
    source: str                     # 'model' | 'measured' | 'cache'


_CACHE: dict[tuple, TuneResult] = {}


def clear_cache() -> None:
    _CACHE.clear()


def _cache_key(plan: SystolicPlan, shape: tuple[int, ...], time_steps: int,
               context: tuple = (), backend: str = "tpu"):
    # jax.default_backend() is the device *platform* (cpu/tpu/gpu host);
    # ``backend`` is the engine lowering ('tpu'/'gpu' kernel shape) —
    # both dimensions key winners, e.g. interpret-mode GPU lowering on a
    # CPU host is (platform='cpu', backend='gpu').
    return (plan, tuple(shape), time_steps, jax.default_backend(), context,
            backend)


# ---------------------------------------------------------------------------
# JSON sidecar persistence + nearest-shape seeding
# ---------------------------------------------------------------------------

def plan_signature(plan: SystolicPlan) -> str:
    """Stable cross-process identity of a plan's schedule + geometry.

    Adjoint plans key apart automatically: ``core.adjoint`` derives
    backward plans with ``adj_``/``wgrad_``-prefixed kinds and
    reflected taps / swapped lead-trail, so a backward-input winner
    never replays a forward winner (and vice versa) in the cache or the
    sidecar — the adjoint is a different kernel with its own block
    optimum (DESIGN.md §10.3).
    """
    digest = hashlib.sha1(repr(plan).encode()).hexdigest()[:16]
    return f"{plan.kind}-{digest}"


def _jsonable(obj):
    if isinstance(obj, (tuple, list)):
        return [_jsonable(o) for o in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def _sidecar_key(sig: str, shape, time_steps: int, context: tuple,
                 strategy: str = "auto", backend: str = "tpu") -> str:
    # strategy is the *plan's* pinned strategy (or 'auto'): a plan pinned
    # to 'mxu' must never replay — or seed from — winners tuned while the
    # tuner was free to pick, and vice versa. backend (v6, seventh
    # component) is the engine lowering the winner was measured against:
    # a GPU warp-tile winner means nothing to the TPU kernel and vice
    # versa, so winners never cross backends.
    return json.dumps([sig, list(shape), time_steps, jax.default_backend(),
                       _jsonable(context), strategy, backend])


# sidecar key → (KernelConfig, model_cost, measured_us)
_SIDECAR: dict[str, tuple[KernelConfig, float, float | None]] = {}
# Measurement spread (IQR µs across reps) rides in a parallel map rather
# than widening the tuple above: tests and checkpoint code construct /
# unpack 3-tuples directly, and spread is v7 metadata, not identity.
_SIDECAR_SPREAD: dict[str, float] = {}


def sidecar_path() -> str | None:
    return os.environ.get(SIDECAR_ENV) or None


def entry_crc(val: dict) -> str:
    """Per-entry checksum over the fields that make a winner a winner.

    Computed over the canonical JSON of the identity-bearing fields (not
    the raw file bytes), so a sidecar re-serialized with different
    whitespace/key order still verifies, while a flipped block size or
    strategy does not."""
    payload = json.dumps([
        _jsonable(val.get("block")), val.get("variant"), val.get("strategy"),
        val.get("model_cost"), val.get("measured_us"), val.get("schema"),
    ])
    return format(zlib.crc32(payload.encode()), "08x")


def _entry_ok(val: dict) -> bool:
    """Schema + checksum gate shared by every sidecar ingest path.

    Wrong-schema entries are *stale* (measured against a different
    lowering); entries whose stored ``crc`` disagrees with the recomputed
    one are *corrupt* (bit-rotted or hand-edited). Entries with no crc at
    all pass — pre-hardening v7 sidecars (and tests that hand-write
    entries) stay loadable; they pick up checksums on the next save."""
    if not isinstance(val, dict) or val.get("schema", 1) != ENGINE_SCHEMA_VERSION:
        obs.metrics.inc("tuner.sidecar_stale")
        return False
    if "crc" in val and val["crc"] != entry_crc(val):
        obs.metrics.inc("tuner.sidecar_corrupt_entry")
        return False
    return True


def _quarantine_sidecar(path: str, err: Exception,
                        on_corrupt: str | None) -> int:
    """Handle an unreadable/corrupt sidecar *file* per policy.

    ``'raise'`` surfaces a :class:`SidecarError` naming the site;
    ``'quarantine'`` renames the file to ``<path>.corrupt`` (so the next
    save starts fresh and the evidence survives for inspection), bumps
    ``tuner.sidecar_quarantined`` and reports zero entries loaded.
    ``None`` resolves from the session failure policy."""
    mode = on_corrupt
    if mode is None:
        mode = "raise" if rguard.on_failure() == "raise" else "quarantine"
    if mode == "raise":
        raise SidecarError(
            f"tuning.sidecar.load: corrupt/unreadable sidecar {path!r}: "
            f"{type(err).__name__}: {err}") from err
    obs.metrics.inc("tuner.sidecar_quarantined")
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass    # already gone / unwritable dir: fresh start regardless
    return 0


def load_sidecar(path: str, *, on_corrupt: str | None = None) -> int:
    """Merge a sidecar file into the persistent store; returns #entries.

    Entries whose ``schema`` does not match :data:`ENGINE_SCHEMA_VERSION`
    are *stale* — measured against a different engine lowering — and are
    skipped (the next :func:`save_sidecar` rewrites the file without
    them, so staleness ages out rather than accumulating). Entries whose
    per-entry checksum fails, or that are structurally broken, are
    skipped individually (``tuner.sidecar_corrupt_entry``). A file that
    cannot be parsed at all goes through :func:`_quarantine_sidecar`:
    under ``on_corrupt='quarantine'`` (or failure policy 'fallback') it
    is renamed ``*.corrupt`` and loading reports 0 entries; under
    ``'raise'`` a :class:`SidecarError` names the site.
    """
    try:
        rfaults.check("tuning.sidecar.load")
        with open(path) as f:
            doc = json.load(f)
        entries = doc.get("entries", {})
        if not isinstance(entries, dict):
            raise ValueError("sidecar 'entries' is not an object")
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        return _quarantine_sidecar(path, e, on_corrupt)
    n = 0
    with obs.span("tuner.load_sidecar", cat="tuner", path=path):
        for key, val in entries.items():
            if not _entry_ok(val):
                continue
            try:
                cfg = KernelConfig(tuple(val["block"]),
                                   val.get("variant", "shift_psum"),
                                   val.get("strategy"))
            except (KeyError, TypeError):
                obs.metrics.inc("tuner.sidecar_corrupt_entry")
                continue
            _SIDECAR[key] = (cfg, val.get("model_cost", 0.0),
                             val.get("measured_us"))
            if val.get("spread_us") is not None:
                _SIDECAR_SPREAD[key] = float(val["spread_us"])
            n += 1
    obs.metrics.inc("tuner.sidecar_load", n=n)
    return n


def _wire_entry(key: str, cfg: KernelConfig, cost, us) -> dict:
    """One sidecar entry in wire format, checksum stamped last."""
    val = {"block": list(cfg.block), "variant": cfg.variant,
           "strategy": cfg.strategy,
           "model_cost": cost, "measured_us": us,
           "spread_us": _SIDECAR_SPREAD.get(key),
           "schema": ENGINE_SCHEMA_VERSION}
    val["crc"] = entry_crc(val)
    return val


def save_sidecar(path: str | None = None) -> str | None:
    """Atomically write the persistent store to ``path`` (or the env path).

    Re-merges the file first so concurrent processes sharing one sidecar
    keep each other's winners (this process's entries win conflicts);
    an unreadable pre-existing file is counted (``tuner.sidecar_remerge_
    failed``) and overwritten — the atomic tmp+rename means a failed
    *write* never destroys the old file. Write failures follow the
    failure policy: 'raise' surfaces a :class:`SidecarError` naming the
    ``tuning.sidecar.save`` site, 'fallback' counts
    ``tuner.sidecar_save_failed`` and keeps the process alive (the store
    is still in memory; the next save retries).
    """
    path = path or sidecar_path()
    if not path:
        return None
    if os.path.exists(path):
        try:
            load_file_only = json.load(open(path)).get("entries", {})
            for key, val in load_file_only.items():
                # Stale-schema / corrupt entries are dropped here:
                # ignored on load, not re-merged on save — the rewrite
                # ages them out.
                if not _entry_ok(val):
                    continue
                if key not in _SIDECAR:
                    _SIDECAR[key] = (
                        KernelConfig(tuple(val["block"]),
                                     val.get("variant", "shift_psum"),
                                     val.get("strategy")),
                        val.get("model_cost", 0.0), val.get("measured_us"))
                    if val.get("spread_us") is not None:
                        _SIDECAR_SPREAD[key] = float(val["spread_us"])
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception:
            # unreadable file: overwrite with our entries, but visibly
            obs.metrics.inc("tuner.sidecar_remerge_failed")
    entries = {
        key: _wire_entry(key, cfg, cost, us)
        for key, (cfg, cost, us) in sorted(_SIDECAR.items())
    }
    try:
        rfaults.check("tuning.sidecar.save")
        tmp = f"{path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": entries}, f, indent=1)
        os.replace(tmp, path)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        if rguard.on_failure() == "raise":
            raise SidecarError(
                f"tuning.sidecar.save: failed writing sidecar {path!r}: "
                f"{type(e).__name__}: {e}") from e
        obs.metrics.inc("tuner.sidecar_save_failed")
        return None
    return path


def _sidecar_store(skey: str, result: TuneResult) -> None:
    """Write-through of a measured winner — only when persistence is on
    (env path set or a sidecar explicitly loaded), so that without a
    sidecar the tuner's in-process behavior is unchanged."""
    if not sidecar_path() and not _SIDECAR:
        return
    _SIDECAR[skey] = (result.config, result.model_cost, result.measured_us)
    if sidecar_path():
        save_sidecar()


def tile_aligned(plan: SystolicPlan, cfg: KernelConfig, shape,
                 time_steps: int = 1) -> bool:
    """Whether every grid offset of ``cfg`` on ``shape`` is a whole number
    of TPU (8, 128) tiles: each of the block's last two dims is a tile
    multiple or covers its whole output extent (a one-step grid).

    Every candidate :func:`candidate_configs` builds for the TPU grid is;
    a neighbor's winner replayed by nearest-shape seeding need not be —
    a block clamped to a small shape's 96-lane extent tiles a wider one
    at 96-lane offsets, which Mosaic refuses.
    """
    if plan.combine != "fma":
        out, block = tuple(shape)[-2:], cfg.block[:2]
    else:
        spatial = tuple(shape)[plan.batch_axes + plan.reduce_axes:]
        out, block = plan.out_shape(spatial, time_steps), cfg.block
    return all(b >= o or b % t == 0
               for b, o, t in zip(block[-2:], out[-2:], (8, 128)))


def _nearest_sidecar(sig: str, shape, time_steps: int, context: tuple,
                     strategy: str = "auto", backend: str = "tpu",
                     usable: Callable[[KernelConfig], bool] | None = None,
                     ) -> KernelConfig | None:
    """The winner of the closest already-tuned shape of the same plan.

    Same plan signature, time_steps, platform, context, pinned
    strategy **and engine backend** — a neighbor tuned under a different
    strategy pin ran a different algorithm, and one tuned against the
    other backend ran a different kernel entirely, so neither may seed
    this one (the v5/v6 key components exist precisely to enforce that).
    Closest by summed |log| ratio of extents, among the winners
    ``usable`` accepts (the TPU tuner passes :func:`tile_aligned`).
    Seeding replays that winner with no measurement — the engine clamps
    blocks to the output shape.
    """
    want = [sig, time_steps, jax.default_backend(), _jsonable(context),
            strategy, backend]
    best, best_d = None, None
    for key, (cfg, _, _) in _SIDECAR.items():
        try:
            ksig, kshape, kt, kplat, kctx, kstrat, kback = json.loads(key)
        except ValueError:      # pre-v6 key arity smuggled past the
            continue            # schema gate: never a seed candidate
        if ([ksig, kt, kplat, kctx, kstrat, kback] != want
                or len(kshape) != len(shape)
                or (usable is not None and not usable(cfg))):
            continue
        d = sum(abs(math.log(k / s)) for k, s in zip(kshape, shape))
        if best_d is None or d < best_d:
            best, best_d = cfg, d
    return best


def clear_sidecar() -> None:
    _SIDECAR.clear()
    _SIDECAR_SPREAD.clear()


def sidecar_entries() -> dict:
    """The persistent store as a JSON-ready entries dict (schema-stamped,
    same wire format as :func:`save_sidecar`). Checkpoints embed this so
    tuned winners survive host moves (DESIGN.md §13)."""
    return {
        key: _wire_entry(key, cfg, cost, us)
        for key, (cfg, cost, us) in sorted(_SIDECAR.items())
    }


def merge_sidecar_entries(entries: dict) -> int:
    """Merge checkpoint-shipped entries into the store; returns #merged.

    Mirrors :func:`load_sidecar`'s staleness + checksum rules
    (wrong-schema or crc-failing entries are skipped) but **never
    clobbers** an existing key: the live process's winners — possibly
    measured on *this* host — outrank whatever the checkpoint carried.
    Does not write through to the env sidecar; the next measured winner
    does, via the usual path.
    """
    n = 0
    for key, val in (entries or {}).items():
        if not _entry_ok(val) or key in _SIDECAR:
            continue
        cfg = KernelConfig(tuple(val["block"]), val.get("variant", "shift_psum"),
                           val.get("strategy"))
        _SIDECAR[key] = (cfg, val.get("model_cost", 0.0), val.get("measured_us"))
        if val.get("spread_us") is not None:
            _SIDECAR_SPREAD[key] = float(val["spread_us"])
        n += 1
    return n


# Import must never break on a bad sidecar, whatever the failure policy:
# force quarantine mode here (rename *.corrupt + counter + fresh start)
# instead of the old silent `except Exception` swallow.
if sidecar_path() and os.path.exists(sidecar_path()):
    load_sidecar(sidecar_path(), on_corrupt="quarantine")


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def candidate_configs(
    plan: SystolicPlan,
    shape: Sequence[int],
    time_steps: int = 1,
    *,
    vmem_budget: int = VMEM_BUDGET_ELEMS,
    chunked: bool = False,
    backend: str = "tpu",
) -> list[KernelConfig]:
    """Feasible block configs for ``plan`` on a problem of ``shape``.

    Blocks are clamped to the output shape, deduplicated, and filtered by
    the VMEM working-set budget (input block + halo, widened by temporal
    blocking). Scan plans tune (block_r, block_t) with power-of-two lane
    tiles; ``chunked=True`` (the streamed schedule, DESIGN.md §12) grows
    a third chunk-length dimension — whole multiples of the lane tile, so
    every candidate passes the chunk-geometry guards; windowed plans tune
    the output tile, the schedule variant, and — when the plan leaves
    ``strategy`` unpinned — the lowering *algorithm* ('lanes' vs 'mxu',
    DESIGN.md §13). MXU candidates carry one canonical variant: the
    im2row views are static crops, so the psum/data-stationary knob is
    moot under that strategy and enumerating both would make the runner
    time the identical kernel twice.

    ``backend`` selects the grid family (DESIGN.md §14): the TPU grids
    are sublane/lane-tiled (8×128-shaped), the GPU grids warp-multiple
    pow2 tiles clamped by the Triton ``next_pow2`` idiom (a tile may
    overhang the output; the grid round-up masks the overhang) so every
    candidate keeps whole-warp shuffle decompositions. Scan tiles are
    already pow2 warp multiples and are shared across backends.
    """
    if plan.combine != "fma":                       # scan family
        R, T = shape
        out: list[KernelConfig] = []
        for br in _SCAN_BLOCK_R:
            for bt in _SCAN_BLOCK_T:
                # a sequence shorter than the tile runs as one padded
                # tile (ssam_scan._lane_tile): every grid offset stays
                # a whole number of 128-lane TPU tiles
                bt_eff = min(bt, _next_pow2(T))
                if not chunked:
                    cfg = KernelConfig((min(br, R), bt_eff))
                    if cfg.block[0] * cfg.block[1] <= vmem_budget:
                        out.append(cfg)
                    continue
                for mult in _SCAN_CHUNK_TILES:      # chunk = m lane tiles
                    chunk = bt_eff * mult
                    if chunk > max(T, bt_eff):
                        continue
                    cfg = KernelConfig((min(br, R), bt_eff, chunk))
                    if cfg.block[0] * chunk <= vmem_budget:
                        out.append(cfg)
        return sorted(set(out), key=lambda c: c.block)

    spatial = tuple(shape)[plan.batch_axes + plan.reduce_axes:]
    out_sp = plan.out_shape(spatial, time_steps)
    gpu = backend == "gpu"
    axes: list[tuple[int, ...]] = []
    if plan.ndim_spatial == 3:
        axes.append(_GPU_BLOCK_Z if gpu else _WINDOW_BLOCK_Z)
    axes.append(_GPU_BLOCK_H if gpu else _WINDOW_BLOCK_H)
    axes.append(_GPU_BLOCK_W if gpu else _WINDOW_BLOCK_W)
    # TPU clamps a candidate to the output extent; GPU clamps to the
    # next pow2 ≥ the extent (tile-chooser idiom) so tiles stay
    # warp-decomposable — the engine's own min(b, out) does the rest.
    clamp = ((lambda b, o: min(b, _next_pow2(o))) if gpu
             else (lambda b, o: min(b, o)))
    if any(v > 1 for v in plan.stride_per_axis()):
        # strided grids use the data-stationary strided read — the
        # variant knob does not apply.
        variants = ("shift_data",)
    else:
        variants = (("shift_psum", "shift_data") if plan.shift_count()
                    else ("shift_psum",))

    if plan.strategy is None:
        # Auto: the tuner owns the algorithm choice. Strategies are
        # explicit on the candidates so a sidecar replay of the winner
        # pins the same lowering on a later, untuned process.
        strat_opts = [("lanes", variants), ("mxu", variants[:1])]
    elif plan.strategy == "mxu":
        # Pinned: candidates restate the pin (so measurement closures
        # that rebuild the plan from kwargs lower the pinned kernel);
        # only the variant knob remains, and under 'mxu' that too
        # collapses to one canonical value.
        strat_opts = [("mxu", variants[:1])]
    else:
        strat_opts = [("lanes", variants)]

    configs: set[KernelConfig] = set()
    def rec(i: int, acc: tuple[int, ...]):
        if i == len(axes):
            if math.prod(plan.block_in_shape(acc, time_steps)) > vmem_budget:
                return
            for s, svariants in strat_opts:
                for v in svariants:
                    configs.add(KernelConfig(acc, v, s))
            return
        for b in axes[i]:
            rec(i + 1, acc + (clamp(b, out_sp[i]),))
    rec(0, ())
    return sorted(configs, key=lambda c: (c.block, c.variant, c.strategy or ""))


# ---------------------------------------------------------------------------
# §5-model pricing
# ---------------------------------------------------------------------------

def model_cost(
    plan: SystolicPlan,
    cfg: KernelConfig,
    time_steps: int = 1,
    hw: HardwareLatencies | None = None,
    *,
    backend: str | None = None,
) -> float:
    """Estimated cycles per useful output element for one block config.

    ``hw`` prices against an explicit latency row; when None it resolves
    from the machine registry for ``backend``
    (:func:`repro.core.perfmodel.machine_for` — 'tpu' → TPU_V5E, 'gpu' →
    the A100-shaped entry; ``backend=None`` follows the config default).
    Each backend is priced by **its own** machine model, never the
    other's: that per-backend prediction is what BENCH_8 quotes next to
    measurements.

    For reduce plans (NCHW conv) this is the cost of *one channel
    iterate* per output element; the full per-output cost scales by
    ``C_in``, which multiplies every candidate identically and so drops
    out of the ranking (the bench applies the C_in factor when quoting
    absolute predictions).

    A fused pipeline (``plan.stages``) prices as one kernel: the flop
    terms are the *summed* stage MADs/shifts (``plan`` methods sum over
    stages) against a **single** load+store whose redundancy uses the
    chain-widened composite halo — whereas the unfused sequence pays the
    memory term once per stage. Epilogue stages add one VPU op each.
    Output strides shrink useful outputs per loaded element, which
    ``block_in_shape``'s stride term prices automatically.
    """
    if hw is None:
        hw = machine_for(_engine_backend(backend))
    t = time_steps
    if plan.combine != "fma":                       # Kogge–Stone scan
        br, bt = cfg.block[:2]
        steps = math.log2(max(bt, 2))
        ops_per_elem = 2.0 if plan.combine == "linrec" else 1.0
        compute = steps * ops_per_elem * (hw.t_shfl + hw.t_mad + hw.t_reg)
        carry = (hw.t_smem_read + hw.t_mad) / bt    # inter-block carry
        memory = hw.t_gmem_read / plan.S
        if len(cfg.block) == 3:                     # streamed schedule (§12)
            # inter-chunk hand-off: the carry round-trips HBM between the
            # lax.scan steps and the slab is re-sliced per chunk — one
            # extra read + scratch touch amortized over chunk elements.
            carry += (hw.t_gmem_read + hw.t_smem_read) / cfg.block[2]
        return compute + carry + memory

    block = cfg.block
    useful = math.prod(block)
    loaded = math.prod(plan.block_in_shape(block, t))
    memory = (loaded / useful) * hw.t_gmem_read / plan.S
    if (cfg.strategy or plan.strategy) == "mxu":
        # §13 im2row pricing: each alignment-padded tap row costs one
        # staged gather + one MXU MAC; no lane shifts (the views are
        # static crops). Padding is priced like real rows, so small
        # footprints lose to the 8-row floor and wide tap sets win —
        # the shape-dependent flip the strategy dimension exists for.
        stages = plan.stages or (plan,)
        rows = sum(mxu_tap_rows(s.mads_per_output_window()) for s in stages)
        compute = t * rows * (hw.t_mxu_stage + hw.t_mxu_mac)
        compute += plan.epilogue_op_count() * hw.t_mad
        return compute + memory
    mads = plan.mads_per_output_window()
    shifts = plan.shift_count()
    P = block[-2]                                   # rows one roll amortizes
    shfl = hw.t_shfl * (0.5 if cfg.variant == "shift_data" else 1.0)
    compute = t * mads * (hw.t_mad + hw.t_reg) + t * shifts * shfl / max(P, 1)
    compute += plan.epilogue_op_count() * hw.t_mad  # fused output stages
    return compute + memory


# ---------------------------------------------------------------------------
# Measurement + the tuner
# ---------------------------------------------------------------------------

class Measurement(float):
    """A measured median that still *is* its µs float — every existing
    consumer (min/sort/format/JSON) handles it unchanged — but carries
    the sample dispersion: ``spread_us`` is the inter-quartile range
    across reps (0.0 when reps < 3 can't resolve quartiles) and
    ``reps`` the sample count. Monkeypatched stand-ins that return bare
    floats stay legal; readers use ``getattr(us, "spread_us", None)``."""

    __slots__ = ("spread_us", "reps")

    def __new__(cls, median_us: float, spread_us: float = 0.0, reps: int = 1):
        m = super().__new__(cls, median_us)
        m.spread_us = float(spread_us)
        m.reps = int(reps)
        return m


def measure_us(fn: Callable[[], jax.Array],
               reps: int | None = None) -> "Measurement":
    """Median wall-time (µs) of ``fn`` post-warmup.

    ``reps`` defaults to ``$REPRO_MEASURE_REPS`` (else 3) so noisy hosts
    (CI) can buy tighter medians without touching call sites. Returns a
    :class:`Measurement` — a float subclass whose ``spread_us`` (IQR
    across the reps) the tuner persists next to the winner (schema v7)
    and the drift monitor uses to separate noise from model error.

    Unusable samples raise a named :class:`MeasurementError` instead of
    leaking into the ranking: a non-finite warmup output (the kernel
    under time produced NaN/Inf — its speed is meaningless) or a
    non-finite/negative median (a clock anomaly). The tuner's
    per-candidate wrapper converts that into retry-then-quarantine.
    """
    rfaults.check("tuning.measure")
    if reps is None:
        try:
            reps = int(os.environ.get(MEASURE_REPS_ENV, "") or 3)
        except ValueError:
            reps = 3
    reps = max(reps, 1)
    out = fn()
    jax.block_until_ready(out)
    if rguard.has_nonfinite(out):
        raise MeasurementError(
            "tuning.measure: candidate produced non-finite output during "
            "warmup — refusing to rank a kernel that computes garbage")
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    median = ts[len(ts) // 2] * 1e6
    iqr = (ts[(3 * (len(ts) - 1)) // 4] - ts[(len(ts) - 1) // 4]) * 1e6
    if not math.isfinite(median) or median < 0:
        raise MeasurementError(
            f"tuning.measure: non-finite/negative median {median!r} µs "
            f"across {reps} reps")
    return Measurement(median, iqr, reps)


def _measure_candidate(runner, cfg: KernelConfig, *, backend: str,
                       retries: int | None = None):
    """One candidate through the hardened measurement path (§16.4).

    Retry-with-backoff on failure, one extra re-measurement when the
    sample is an IQR outlier (spread > ``OUTLIER_SPREAD_FRACTION`` of
    the median — a noisy sample must not decide the ranking), and
    quarantine (returns ``None``) when every attempt fails — so one bad
    candidate can neither win nor abort the sweep. Under
    ``on_failure='raise'`` an injected fault or measurement error
    surfaces immediately as a structured :class:`GuardedExecutionError`;
    organic exceptions re-raise unchanged.
    """
    if retries is None:
        try:
            retries = int(os.environ.get(MEASURE_RETRIES_ENV, "") or 2)
        except ValueError:
            retries = 2
    backoff = 0.005
    for attempt in range(retries + 1):
        try:
            us = runner(cfg)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            if rguard.on_failure() == "raise":
                if isinstance(e, (rfaults.FaultInjected, MeasurementError)):
                    raise rguard.GuardedExecutionError(
                        "tuner.measure", [(f"candidate {cfg.block}", e)]
                    ) from e
                raise
            obs.metrics.inc("tuner.measure_retry", backend)
            if attempt < retries:
                time.sleep(backoff * (2 ** attempt))
            continue
        m = float(us)
        if not math.isfinite(m) or m < 0:
            # runner bypassed measure_us (bare-float stand-ins): apply
            # the same rejection here so garbage never enters min().
            obs.metrics.inc("tuner.measure_nonfinite", backend)
            continue
        spread = getattr(us, "spread_us", 0.0) or 0.0
        if spread > OUTLIER_SPREAD_FRACTION * max(m, 1e-9) and attempt < retries:
            obs.metrics.inc("tuner.measure_outlier", backend)
            continue
        return us
    obs.metrics.inc("tuner.quarantined", backend)
    return None


def autotune(
    plan: SystolicPlan,
    shape: Sequence[int],
    *,
    time_steps: int = 1,
    default: KernelConfig | None = None,
    runner: Callable[[KernelConfig], float] | None = None,
    hw: HardwareLatencies | None = None,
    top_k: int = 3,
    context: tuple = (),
    fixed: dict | None = None,
    chunked: bool = False,
    backend: str | None = None,
) -> TuneResult:
    """Pick a block config for ``plan`` on ``shape``.

    Ranks candidates by :func:`model_cost`; when ``runner`` is given
    (a ``cfg → µs`` measurement closure) the model's top-k **plus the
    default config** are measured and the measured winner is returned —
    so the result can never regress the default on the measured metric.
    Winners are cached per (plan, shape, time_steps, backend, context);
    ``context`` must capture anything else that changes what the runner
    actually measures (caller-forced kwargs, op mode, impl), otherwise a
    winner measured under one context is replayed under another.

    ``backend`` is the engine lowering being tuned ('tpu'/'gpu'/'auto';
    None follows the config default): it selects the candidate grid
    family and — unless ``hw`` overrides — the machine model, and it
    keys the cache and the v6 sidecar so winners never cross backends
    (DESIGN.md §14). The caller's runner must lower with the same
    backend, or the recorded winner prices one kernel and replays
    another.

    ``fixed`` names kwargs the caller pins (they override the candidate
    at run time): candidates are restricted to those agreeing with the
    pinned values — and deduplicated by their *effective* kwargs — so the
    runner never measures the same kernel twice and the recorded winner
    is the config that actually ran.
    """
    backend = _engine_backend(backend)
    if hw is None:
        hw = machine_for(backend)
    key = _cache_key(plan, tuple(shape), time_steps, context, backend)
    if key in _CACHE:
        obs.metrics.inc("tuner.cache_hit", backend)
        cached = _CACHE[key]
        return dataclasses.replace(cached, source="cache")

    def _agrees(cfg: KernelConfig) -> bool:
        return not fixed or all(
            cfg.as_kwargs(plan).get(k, v) == v for k, v in fixed.items())

    if (default is not None and default.strategy is None
            and plan.combine == "fma" and plan.strategy is not None):
        # Under a pinned plan every measured config runs the pinned
        # lowering anyway — restate the pin on the default (as
        # candidate_configs does) so a default win records a config
        # whose strategy matches its sidecar key.
        default = dataclasses.replace(default, strategy=plan.strategy)

    sig = plan_signature(plan)
    pstrat = (plan.strategy or "auto") if plan.combine == "fma" else "auto"
    skey = _sidecar_key(sig, shape, time_steps, context, pstrat, backend)
    hit = _SIDECAR.get(skey)
    if hit is not None and _agrees(hit[0]):
        obs.metrics.inc("tuner.sidecar_hit", backend)
        result = TuneResult(hit[0], hit[1], hit[2], "sidecar")
        _CACHE[key] = result
        return result
    with obs.span("tuner.seed", cat="tuner", plan=sig, backend=backend):
        usable = (None if backend == "gpu" else
                  lambda c: tile_aligned(plan, c, shape, time_steps))
        seed = _nearest_sidecar(sig, shape, time_steps, context, pstrat,
                                backend, usable)
    if seed is not None and _agrees(seed):
        obs.metrics.inc("tuner.sidecar_seed", backend)
        result = TuneResult(seed, model_cost(plan, seed, time_steps, hw),
                            None, "seeded")
        _CACHE[key] = result
        return result
    obs.metrics.inc("tuner.sidecar_miss", backend)

    with obs.span("tuner.candidates", cat="tuner", plan=sig, backend=backend):
        cands = candidate_configs(plan, shape, time_steps, chunked=chunked,
                                  backend=backend)
    if default is not None and default not in cands:
        cands.append(default)
    if fixed:
        agreeing = [c for c in cands
                    if all(c.as_kwargs(plan).get(k, v) == v
                           for k, v in fixed.items())]
        if agreeing:
            cands = agreeing
        else:      # pinned value outside the grid: dedupe by what runs
            seen: dict[tuple, KernelConfig] = {}
            for c in cands:
                eff = tuple(sorted({**c.as_kwargs(plan), **fixed}.items()))
                seen.setdefault(eff, c)
            cands = list(seen.values())
    if not cands:
        raise ValueError(f"no feasible block configs for {plan.kind} {shape}")
    ranked = sorted(cands, key=lambda c: model_cost(plan, c, time_steps, hw))

    if runner is None:
        best = ranked[0]
        result = TuneResult(best, model_cost(plan, best, time_steps, hw),
                            None, "model")
    else:
        if plan.combine == "fma" and plan.strategy is None:
            # Open algorithm choice (DESIGN.md §13): measure the model's
            # top-k of EACH strategy present, not the global top-k — the
            # model proposes a per-strategy shortlist, measurement gets
            # the final say *across* algorithms. A global top-k could be
            # one strategy wall-to-wall and silently never time the
            # other lowering on this hardware.
            by_strat: dict[str | None, list[KernelConfig]] = {}
            for c in ranked:
                by_strat.setdefault(c.strategy, []).append(c)
            to_measure = [c for group in by_strat.values()
                          for c in group[:top_k]]
        else:
            to_measure = list(ranked[:top_k])
        if default is not None and default not in to_measure:
            to_measure.append(default)
        try:
            budget_s = float(os.environ.get(TUNE_BUDGET_ENV, "") or 0.0)
        except ValueError:
            budget_s = 0.0
        deadline = (time.monotonic() + budget_s) if budget_s > 0 else None
        timed = []
        for idx, c in enumerate(to_measure):
            if deadline is not None and timed and time.monotonic() > deadline:
                # Wall-clock budget exhausted: rank what we have. Never
                # skip the *first* candidate — a budget too small to
                # measure anything would silently become model-only.
                obs.metrics.inc("tuner.budget_skipped", backend,
                                n=len(to_measure) - idx)
                break
            with obs.span("tuner.measure", cat="tuner", plan=sig,
                          backend=backend, block=list(c.block),
                          variant=c.variant, strategy=c.strategy or "auto"):
                us_c = _measure_candidate(runner, c, backend=backend)
            if us_c is None:
                continue        # quarantined: neither wins nor aborts
            obs.metrics.inc("tuner.measure", backend)
            # Every measured candidate is a free (predicted, measured)
            # drift sample — not just the winner (DESIGN.md §15).
            obs.drift.record(sig, backend, c.strategy,
                             model_cost(plan, c, time_steps, hw),
                             float(us_c), shape=tuple(shape))
            timed.append((us_c, c))
        if not timed:
            # Every measurement quarantined: fall back to the model's
            # ranking rather than crashing the sweep — the §5 model is
            # exactly the prior we keep for when measurement is broken.
            obs.metrics.inc("tuner.model_fallback", backend)
            best = ranked[0]
            result = TuneResult(best, model_cost(plan, best, time_steps, hw),
                                None, "model_fallback")
        else:
            us, best = min(timed, key=lambda p: p[0])
            result = TuneResult(best, model_cost(plan, best, time_steps, hw),
                                us, "measured")
            _sidecar_store(skey, result)
            spread = getattr(us, "spread_us", None)
            if spread is not None and skey in _SIDECAR:
                _SIDECAR_SPREAD[skey] = float(spread)
    _CACHE[key] = result
    return result


# ---------------------------------------------------------------------------
# Shard-local tuning
# ---------------------------------------------------------------------------

def shard_tuning_shape(
    plan: SystolicPlan,
    global_spatial: Sequence[int],
    mesh_per_axis: Sequence[tuple[str, int] | None],
    time_steps: int = 1,
    boundary: str = "zero",
) -> tuple[int, ...]:
    """The halo-extended shard-local shape a sharded run lowers per device.

    This — not the global shape — is what per-shard block configs must
    be tuned against: the engine inside ``shard_map`` sees
    ``local + halo_lo + halo_hi`` rows per sharded axis (under
    'wrap'/'replicate' boundaries, per *every* axis — unsharded axes
    halo-extend locally too). A winner measured on this shape is the
    monolithic (``overlap=False``) per-device lowering; the overlapped
    schedule decomposes the same data volume into an interior call on
    the un-extended block plus thin frame strips, so the measured
    ranking carries over while absolute times differ by the frame
    recompute. Raises the same :class:`ValueError`\\ s as the sharded
    path itself (indivisible mesh axis, shard smaller than the halo).
    """
    from .halo import check_shard_geometry, shard_halo
    local = check_shard_geometry(
        plan, tuple(global_spatial), tuple(mesh_per_axis), time_steps)
    halos = shard_halo(plan, time_steps)
    return tuple(
        n + (lo + hi
             if boundary != "zero" or (assign is not None and assign[1] > 1)
             else 0)
        for n, assign, (lo, hi) in zip(local, mesh_per_axis, halos))
