"""Architecture registry + input_specs for every (arch x shape) cell."""
from __future__ import annotations

import importlib
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCfg, SHAPES

ARCH_IDS = [
    "deepseek_moe_16b",
    "deepseek_v2_236b",
    "llava_next_mistral_7b",
    "seamless_m4t_large_v2",
    "yi_34b",
    "starcoder2_3b",
    "qwen3_14b",
    "mistral_nemo_12b",
    "zamba2_7b",
    "mamba2_130m",
    # extras, not assigned cells: the paper's own benchmark model and the
    # benchmark's MLA + MoE configuration
    "llama2_7b",
    "deepseek_v2_lite",
]
N_EXTRA = 2

# assignment ids use dashes
_ALIASES = {a.replace("_", "-"): a for a in ARCH_IDS}


def _module(name: str):
    name = _ALIASES.get(name, name)
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    mod = _module(name)
    return mod.reduced() if reduced else mod.full()


def list_archs(include_extra: bool = False) -> List[str]:
    return ARCH_IDS if include_extra else ARCH_IDS[:-N_EXTRA]


def list_draft_profiles() -> List[str]:
    """Draft compression profiles for speculative decoding (the serving
    CLIs' --draft-profile choices). Lazy import: configs stay importable
    without the compression stack."""
    from repro.core.model_compress import DRAFT_PROFILES
    return sorted(DRAFT_PROFILES)


def supported_shapes(cfg: ModelConfig) -> List[str]:
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.supports_long_context:
        out.append("long_500k")
    return out


def input_specs(cfg: ModelConfig, shape: ShapeCfg) -> Dict:
    """ShapeDtypeStruct stand-ins for a forward/train call (no allocation).

    For decode shapes these are the *per-step* token inputs; the cache specs
    come from jax.eval_shape(api.init_cache, ...) in the launcher.
    """
    sds = jax.ShapeDtypeStruct
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = cfg.compute_dtype

    if shape.kind == "decode":
        return {"tokens": sds((b, 1), i32)}

    batch: Dict = {}
    if cfg.family == "vlm":
        s_text = s - cfg.n_patches
        batch["tokens"] = sds((b, s_text), i32)
        batch["patch_embeds"] = sds((b, cfg.n_patches, cfg.d_model), dt)
        if shape.kind == "train":
            batch["labels"] = sds((b, s_text), i32)
        return batch
    if cfg.family == "encdec":
        batch["tokens"] = sds((b, s), i32)
        batch["frames"] = sds((b, cfg.n_frames, cfg.d_model), dt)
        if shape.kind == "train":
            batch["labels"] = sds((b, s), i32)
        return batch
    batch["tokens"] = sds((b, s), i32)
    if shape.kind == "train":
        batch["labels"] = sds((b, s), i32)
    return batch


def all_cells(include_extra: bool = False):
    """Every assigned (arch, shape) pair, with skips annotated."""
    cells = []
    for arch in list_archs(include_extra):
        cfg = get_config(arch)
        for sname, shp in SHAPES.items():
            runnable = sname in supported_shapes(cfg)
            cells.append((arch, sname, runnable))
    return cells
