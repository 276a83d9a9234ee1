"""Architecture registry: ``--arch <id>`` resolution for every entry point."""

from __future__ import annotations

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, applicable, reduced
from repro.configs.deepseek_moe_16b import CONFIG as _deepseek_moe_16b
from repro.configs.deepseek_v3_671b import CONFIG as _deepseek_v3_671b
from repro.configs.deepseek_v3_ep32_5l import CONFIG as _deepseek_v3_ep32_5l
from repro.configs.llava_next_mistral_7b import CONFIG as _llava_next_mistral_7b
from repro.configs.mamba2_1_3b import CONFIG as _mamba2_1_3b
from repro.configs.qwen3_8b import CONFIG as _qwen3_8b
from repro.configs.recurrentgemma_9b import CONFIG as _recurrentgemma_9b
from repro.configs.seamless_m4t_large_v2 import CONFIG as _seamless_m4t_large_v2
from repro.configs.stablelm_3b import CONFIG as _stablelm_3b
from repro.configs.starcoder2_15b import CONFIG as _starcoder2_15b
from repro.configs.yi_9b import CONFIG as _yi_9b

ARCHS: dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _recurrentgemma_9b,
        _yi_9b,
        _stablelm_3b,
        _qwen3_8b,
        _starcoder2_15b,
        _llava_next_mistral_7b,
        _deepseek_v3_671b,
        _deepseek_moe_16b,
        _seamless_m4t_large_v2,
        _mamba2_1_3b,
    )
}


# Serving deployments cut from an architecture above: one chip's share of a
# stated multi-chip deployment.  They resolve by name like an architecture
# but stay outside the (arch x shape) grid.
SHARES: dict[str, ModelConfig] = {c.name: c for c in (_deepseek_v3_ep32_5l,)}


def get_arch(name: str) -> ModelConfig:
    if name.endswith("-smoke"):
        return reduced(get_arch(name[: -len("-smoke")]))
    if name in ARCHS:
        return ARCHS[name]
    if name in SHARES:
        return SHARES[name]
    raise KeyError(f"unknown arch {name!r}; known: "
                   f"{sorted(ARCHS) + sorted(SHARES)}")


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def all_cells() -> list[tuple[ModelConfig, ShapeConfig, bool, str]]:
    """All 40 (arch x shape) cells with applicability verdicts."""
    cells = []
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            ok, why = applicable(arch, shape)
            cells.append((arch, shape, ok, why))
    return cells
