"""A configuration's file and what every architecture has: the program's
registry id, the reference, the served type, the slots and cache length.
The rest of the file is read by the architecture module it names under
``architecture.kind`` (``bench/arch/<kind>.py``), which builds the
program's ModelConfig, the weights and the work counts."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from types import ModuleType
from typing import Any, Dict

import jax
import jax.numpy as jnp

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    program: str            # the program's registry id
    reference: str          # module under bench/reference/
    arch: ModuleType        # module under bench/arch/
    raw: Dict[str, Any]     # the configuration's file as loaded
    dtype: Any
    slots: int
    max_len: int
    shape: Any              # arch.shape(raw)

    @classmethod
    def load(cls, name: str) -> "ModelSpec":
        path = CONFIG_DIR / f"{name}.json"
        raw = json.loads(path.read_text())
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "ModelSpec":
        arch = importlib.import_module(
            f"bench.arch.{raw['architecture']['kind']}")
        return cls(name=raw["name"], program=raw["program"],
                   reference=raw["reference"], arch=arch, raw=raw,
                   dtype=DTYPES[raw["config"]["torch_dtype"]],
                   slots=int(raw["serve"]["slots"]),
                   max_len=int(raw["serve"]["max_len"]),
                   shape=arch.shape(raw))

    def program_config(self):
        """The program's ModelConfig for this file."""
        return self.arch.program_config(self)

    def make_weights(self, key: jax.Array) -> Dict[str, Any]:
        """The program's parameter tree, made on the device from ``key``."""
        return self.arch.make_weights(self, key)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (larger than 32 bits too)."""
    from .traffic import seed_rng

    return jax.random.PRNGKey(int(seed_rng(seed, 0).integers(0, 2 ** 31)))
