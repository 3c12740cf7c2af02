"""Assigned-architecture configs (+ the paper's own ResNet-50).

Each ``<id>.py`` exports ``CONFIG`` (the exact published configuration) and
``REDUCED`` (a same-family small config for CPU smoke tests). ``SHAPES``
defines the assigned input-shape set; :func:`input_specs` in
``repro.launch.dryrun`` materializes them as ShapeDtypeStructs.
"""
from __future__ import annotations

from dataclasses import dataclass
from importlib import import_module
from typing import Dict, List, Optional, Tuple

from ..models.config import ArchConfig

ARCH_IDS = [
    "zamba2_2p7b",
    "qwen3_moe_30b_a3b",
    "llama4_maverick_400b_a17b",
    "deepseek_67b",
    "granite_20b",
    "glm4_9b",
    "gemma2_27b",
    "chameleon_34b",
    "mamba2_130m",
    "whisper_large_v3",
]

# canonical ids as assigned (dashes) -> module names
_ALIASES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "deepseek-67b": "deepseek_67b",
    "granite-20b": "granite_20b",
    "glm4-9b": "glm4_9b",
    "gemma2-27b": "gemma2_27b",
    "chameleon-34b": "chameleon_34b",
    "mamba2-130m": "mamba2_130m",
    "whisper-large-v3": "whisper_large_v3",
}


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def _module(arch: str):
    mod_name = _ALIASES.get(arch, arch.replace("-", "_").replace(".", "p"))
    return import_module(f".{mod_name}", __package__)


def get_config(arch: str, reduced: bool = False) -> ArchConfig:
    mod = _module(arch)
    return mod.REDUCED if reduced else mod.CONFIG


def depth_cut(cfg: ArchConfig, layers: int) -> ArchConfig:
    """The first ``layers`` layers of ``cfg`` at unchanged widths: one
    chip's share of a deployment whose remaining layers would sit on
    further chips as pipeline stages.  ``layers <= 0`` (or at least the
    published depth) keeps every layer.  The cut must keep whole periods of
    the layer pattern (local/global alternation, dense/expert interleave,
    shared-attention spacing) so every kind of layer stays in its ratio."""
    if layers <= 0 or layers >= cfg.num_layers:
        return cfg
    period = max(
        cfg.global_every,
        cfg.moe_every if cfg.family == "moe" else 1,
        cfg.hybrid_attn_every,
        1,
    )
    if layers % period:
        raise ValueError(
            f"{cfg.name}: a {layers}-layer cut splits its {period}-layer "
            f"pattern; pick a multiple of {period}"
        )
    return cfg.replace(name=f"{cfg.name}-{layers}L", num_layers=layers)


def list_archs() -> List[str]:
    return list(_ALIASES)


def shape_applicable(cfg: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell runs; else the documented skip reason."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "SKIP: full-attention arch at 500k decode (see DESIGN.md)"
    return True, ""
