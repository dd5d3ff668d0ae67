"""Config registry: architectures (``--arch``), input shapes (``--shape``),
and per-cell parallelism rule overrides.

Each ``repro/configs/<id>.py`` exports ``CONFIG`` (the exact published
configuration from the assignment) and ``SMOKE`` (a reduced same-family
config for CPU tests). ``SHAPES`` are the four assigned input shapes;
applicability (e.g. ``long_500k`` needs sub-quadratic attention) is
encoded here and surfaced as SKIP rows in the roofline table.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.base import ArchConfig

ARCHS = (
    "rwkv6_1g6b", "stablelm_12b", "chatglm3_6b", "gemma3_1b",
    "starcoder2_3b", "dbrx_132b", "deepseek_v2_236b", "hymba_1g5b",
    "internvl2_1b", "whisper_base",
)

# canonical assignment ids → module names
ARCH_IDS = {
    "rwkv6-1.6b": "rwkv6_1g6b",
    "stablelm-12b": "stablelm_12b",
    "chatglm3-6b": "chatglm3_6b",
    "gemma3-1b": "gemma3_1b",
    "starcoder2-3b": "starcoder2_3b",
    "dbrx-132b": "dbrx_132b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "hymba-1.5b": "hymba_1g5b",
    "internvl2-1b": "internvl2_1b",
    "whisper-base": "whisper_base",
}


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq: int
    batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

# long_500k requires sub-quadratic attention: run for SSM/hybrid/local-
# attention archs, skip pure full-attention archs (DESIGN.md §5).
LONG_CONTEXT_ARCHS = {"rwkv6_1g6b", "hymba_1g5b", "gemma3_1b"}


# ---------------------------------------------------------------------------
# Engine backend default (DESIGN.md §14)
#
# Which *lowering* of the plan IR `run_window_plan`/`run_scan_plan` pick
# when the caller passes backend=None: "tpu" (core/engine.py's
# sublane/lane tiling) or "gpu" (core/engine_gpu.py's warp-shuffle
# tiling). Distinct from jax.default_backend() — that is the device
# platform; this is which kernel *shape* we emit (the GPU lowering runs
# fine in interpret mode on CPU, which is how CI proves equivalence).

ENGINE_BACKENDS = ("tpu", "gpu")
ENGINE_BACKEND_ENV = "REPRO_ENGINE_BACKEND"
_ENGINE_BACKEND: str | None = None


def resolve_engine_backend(backend: str) -> str:
    """Normalize a user-facing backend name; ``auto`` follows the jax
    platform (GPU devices get the GPU lowering, everything else TPU)."""
    if backend == "auto":
        import jax

        return "gpu" if jax.default_backend() == "gpu" else "tpu"
    if backend not in ENGINE_BACKENDS:
        raise ValueError(
            f"unknown engine backend {backend!r}: expected one of "
            f"{ENGINE_BACKENDS + ('auto',)}")
    return backend


def engine_backend() -> str:
    """The session's default engine backend: ``set_engine_backend()`` if
    called, else ``$REPRO_ENGINE_BACKEND``, else ``auto``."""
    import os

    if _ENGINE_BACKEND is not None:
        return _ENGINE_BACKEND
    return resolve_engine_backend(os.environ.get(ENGINE_BACKEND_ENV, "auto"))


def set_engine_backend(backend: str | None) -> None:
    """Pin the process-wide default engine backend (``None`` restores the
    env/auto resolution)."""
    global _ENGINE_BACKEND
    _ENGINE_BACKEND = None if backend is None else resolve_engine_backend(backend)


# ---------------------------------------------------------------------------
# Failure policy (DESIGN.md §16)
#
# What a guarded ops.* dispatch does when an execution level fails:
# 'fallback' walks the degradation lattice (tuned → default → alternate
# strategy/backend → reference oracle), 'raise' surfaces a structured
# error naming the failing site. Same resolution order as the engine
# backend: session global → $REPRO_ON_FAILURE → default 'raise', so a
# kernel that fails is never silently replaced by the XLA oracle; callers
# that want degradation ask for 'fallback' by name.

ON_FAILURE_MODES = ("fallback", "raise")
ON_FAILURE_ENV = "REPRO_ON_FAILURE"
CHECK_NUMERICS_ENV = "REPRO_CHECK_NUMERICS"
_ON_FAILURE: str | None = None
_CHECK_NUMERICS: bool | None = None


def resolve_on_failure(mode: str) -> str:
    if mode not in ON_FAILURE_MODES:
        raise ValueError(
            f"unknown on_failure mode {mode!r}: expected one of {ON_FAILURE_MODES}")
    return mode


def on_failure() -> str:
    """The session's failure policy: ``set_on_failure()`` if called, else
    ``$REPRO_ON_FAILURE``, else ``'raise'``."""
    import os

    if _ON_FAILURE is not None:
        return _ON_FAILURE
    return resolve_on_failure(os.environ.get(ON_FAILURE_ENV, "raise"))


def set_on_failure(mode: str | None) -> None:
    """Pin the process-wide failure policy (``None`` restores env/default)."""
    global _ON_FAILURE
    _ON_FAILURE = None if mode is None else resolve_on_failure(mode)


def check_numerics() -> bool:
    """Opt-in non-finite output detection on guarded dispatches:
    ``set_check_numerics()`` if called, else truthy ``$REPRO_CHECK_NUMERICS``."""
    import os

    if _CHECK_NUMERICS is not None:
        return _CHECK_NUMERICS
    env = os.environ.get(CHECK_NUMERICS_ENV, "")
    return bool(env) and env.lower() not in ("0", "false", "off")


def set_check_numerics(flag: bool | None) -> None:
    global _CHECK_NUMERICS
    _CHECK_NUMERICS = None if flag is None else bool(flag)


def normalize_arch(arch: str) -> str:
    arch = arch.replace("-", "_").replace(".", "g")
    return ARCH_IDS.get(arch, arch)


def get_config(arch: str, *, smoke: bool = False) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{normalize_arch(arch)}")
    return mod.SMOKE if smoke else mod.CONFIG


def cell_applicable(arch: str, shape: str) -> tuple[bool, str]:
    """(runs?, reason-if-skip) for an (arch × shape) cell."""
    arch = normalize_arch(arch)
    if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, "SKIP(full-attn): 500k decode needs sub-quadratic attention"
    return True, ""


def all_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            yield arch, shape


def active_param_count(cfg: ArchConfig) -> tuple[int, int]:
    """(total_params, active_params) — active excludes embeddings and
    counts MoE experts at top_k/n_experts utilization (MODEL_FLOPS = 6·N_active·D)."""
    from repro.models import build_model
    from repro.nn.spec import param_count

    model = build_model(cfg)
    total = param_count(model.specs())
    embed = cfg.vocab * cfg.d_model
    if not cfg.tie_embeddings:
        embed *= 2
    active = total - embed
    if cfg.moe:
        expert_total = 3 * cfg.n_experts * cfg.d_model * cfg.d_ff * cfg.n_layers
        active = active - expert_total + expert_total * cfg.top_k / cfg.n_experts
    return total, int(active)
