"""Architecture-config registry and assigned input-shape definitions.

Each assigned architecture ships one module in this package defining an
:class:`ArchConfig`: the exact published model config, a reduced smoke
config of the same family, shape applicability (e.g. ``long_500k`` only for
sub-quadratic mixers), and the TNN (paper-technique) variant.

``--arch <id>`` resolution goes through :func:`get`.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

from repro.core.tensorized import TNNConfig

ARCH_IDS = [
    "rwkv6_7b", "qwen3_moe_235b_a22b", "olmoe_1b_7b", "llava_next_34b",
    "seamless_m4t_medium", "internlm2_1_8b", "phi4_mini_3_8b",
    "tinyllama_1_1b", "qwen2_7b", "zamba2_7b", "granite_4_0_h_micro",
]

PAPER_IDS = ["paper_atis_tt"]   # UCF LSTM layers live in benchmarks/workloads.py


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str              # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    id: str
    family: str                     # ssm | moe | vlm | audio | dense | hybrid
    model_kind: str                 # "lm" | "encdec"
    make_model: Callable[..., Any]  # (tnn: TNNConfig|None) -> LMConfig/EncDecConfig
    make_smoke: Callable[..., Any]  # reduced same-family config
    input_kind: str = "tokens"      # tokens | embeds (modality stub)
    sub_quadratic: bool = False     # may run long_500k
    notes: str = ""
    # backend="pallas" routes layer contractions through the plan compiler
    # (repro.core.plan_compiler); override per-arch or via train --tnn-backend.
    tnn_default: TNNConfig = TNNConfig(
        enabled=True, method="tt", rank=64, num_factors=2, targets=("mlp",),
        backend="einsum")

    def shape_supported(self, shape: ShapeSpec) -> tuple[bool, str]:
        """(supported, reason-if-skipped) for a dry-run cell."""
        if shape.name == "long_500k" and not self.sub_quadratic:
            return False, ("full quadratic attention: 512Ki-token decode is "
                           "out of scope per assignment (sub-quadratic archs "
                           "only); see DESIGN.md §Arch-applicability")
        return True, ""

    def model(self, tnn: TNNConfig | None = None):
        return self.make_model(tnn=tnn)

    def smoke(self, tnn: TNNConfig | None = None):
        return self.make_smoke(tnn=tnn)


_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.id] = cfg
    return cfg


def get(arch_id: str) -> ArchConfig:
    arch_id = arch_id.replace("-", "_").replace(".", "_")
    if arch_id not in _REGISTRY:
        try:
            importlib.import_module(f"repro.configs.{arch_id}")
        except ImportError as e:
            raise KeyError(
                f"unknown arch {arch_id!r}; known: {ARCH_IDS + PAPER_IDS}"
            ) from e
    return _REGISTRY[arch_id]


def all_archs() -> list[ArchConfig]:
    return [get(a) for a in ARCH_IDS]
