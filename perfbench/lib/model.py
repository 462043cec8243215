"""Model configuration files and the architecture module each names.

A configuration file (``perfbench/configs/<name>.json``) holds the
model as it is run, under the keys of its published ``config.json``.
Its ``model_type`` names the architecture module
``<archs_dir>/<model_type>.py`` that knows its shape keys: the
program's ``ModelConfig``, the seeded weights, the parameter counts,
each kernel's work and the plain reference (``perfbench/archs/``).
This module checks only what every configuration states.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, Tuple

import numpy as np

Json = Dict[str, Any]

GENERIC_KEYS = ("name", "source", "model_type", "vocab_size", "torch_dtype",
                "matmul_precision", "reduced", "param_bytes")


def load_config(path: Path, archs_dir: Path) -> Tuple[Json, ModuleType]:
    """The configuration at ``path`` and its architecture module, found
    by file path under ``archs_dir``; the module checks its own keys."""
    with open(path) as f:
        cj = json.load(f)
    for key in GENERIC_KEYS:
        if key not in cj:
            raise KeyError(f"{path}: configuration lacks {key!r}")
    if cj["torch_dtype"] != "float32":
        raise ValueError(f"{path}: only float32 serving is benchmarked")
    if cj["matmul_precision"] != "default":
        raise ValueError(f"{path}: only JAX's default matmul precision is "
                         "benchmarked")
    mt = cj["model_type"]
    arch_path = Path(archs_dir) / f"{mt}.py"
    if not arch_path.is_file():
        raise FileNotFoundError(f"{path}: no architecture module {arch_path} "
                                f"for model_type {mt!r}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_arch_" + mt.replace("-", "_").replace(".", "_"), arch_path)
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    arch.check(cj)
    return cj, arch


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole-number ``--seed``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])
