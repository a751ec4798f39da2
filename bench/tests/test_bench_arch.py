"""The architecture a configuration names: the dense module gives the
weights and counts the harness had before architectures were modules, and
a new architecture joins as files of its own, with no file of ``bench/``
edited."""

import hashlib
import json
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

from bench.model import ModelSpec, seed_key

from .conftest import TINY_CONFIG

BENCH = pathlib.Path(__file__).resolve().parents[1]


def digest(tree) -> str:
    """sha256 over each leaf's path, type, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Taken from the weights the harness made before the dense code moved into
# bench/arch/dense_gqa.py, on the tiny configuration in bf16.
@pytest.mark.parametrize("qk_norm,seed,want", [
    (True, 7,
     "760afeb9e449e3350b19b65db2d44701e631977b3877e472d58f5a69da3086d7"),
    (True, 2 ** 31 + 3,
     "a6a0a59b97bc0f9a4c71b3b6cb943f57aa21fd4dc9009dd792d154f2a1e35c3f"),
    (False, 7,
     "99f4a776f9e05501bd2f7497a2c48d12a785755a3acb6a9e0c88a1539b91d15e")])
def test_dense_weights_are_bit_identical_to_before(qk_norm, seed, want):
    spec = ModelSpec.from_dict({
        **TINY_CONFIG,
        "architecture": {"kind": "dense_gqa", "qk_norm": qk_norm}})
    assert digest(spec.make_weights(seed_key(seed))) == want


# Read from the counts the harness made before the move (its
# request_model_flops over the same configuration files).
@pytest.mark.parametrize("name,prompt_len,decoded,want", [
    ("smollm-135m", 128, 511, 178807799808.0),
    ("smollm-135m", 1536, 31, 419459309568.0),
    ("qwen3-14b", 128, 511, 4207086141440.0),
    ("qwen3-14b", 1536, 31, 8532386119680.0)])
def test_request_flops_are_unchanged(name, prompt_len, decoded, want):
    spec = ModelSpec.load(name)
    assert spec.shape.request_model_flops(prompt_len, decoded) == want


def test_both_configurations_name_the_dense_module():
    for name in ("smollm-135m", "qwen3-14b"):
        spec = ModelSpec.load(name)
        assert spec.arch.__name__ == "bench.arch.dense_gqa"
        assert spec.raw["architecture"]["kind"] == "dense_gqa"


DENSE_WORDS = ("DenseShape", "intermediate_size", "qk_norm", "rope_theta",
               "num_key_value_heads")


@pytest.mark.parametrize("word", DENSE_WORDS)
def test_only_architecture_modules_name_dense_keys(word):
    """The harness, the readers and the loaders depend only on what every
    architecture has."""
    owners = {BENCH / "arch", BENCH / "reference", BENCH / "tests"}
    named = [str(p.relative_to(BENCH)) for p in BENCH.rglob("*.py")
             if not owners & set(p.parents) and word in p.read_text()]
    assert named == []


# A second architecture, as a later configuration would bring it: the dense
# decoder under another name, with a shape of its own whose projections are
# the attention's only, and a count of each call the harness makes into it.
NEW_ARCH = '''
import dataclasses

from bench.arch import dense_gqa

CALLS = {"program_config": 0, "make_weights": 0, "shape": 0,
         "request_model_flops": 0}


@dataclasses.dataclass(frozen=True)
class Shape(dense_gqa.DenseShape):
    def projections(self):
        return dense_gqa.DenseShape.projections(self)[:4]

    def request_model_flops(self, prompt_len, decoded):
        CALLS["request_model_flops"] += 1
        return dense_gqa.DenseShape.request_model_flops(self, prompt_len,
                                                        decoded)


def shape(raw):
    CALLS["shape"] += 1
    return Shape(**dataclasses.asdict(dense_gqa.shape(raw)))


def program_config(spec):
    CALLS["program_config"] += 1
    return dense_gqa.program_config(spec)


def make_weights(spec, key):
    CALLS["make_weights"] += 1
    return dense_gqa.make_weights(spec, key)
'''


@pytest.fixture
def new_arch(tiny_cell, tmp_path, monkeypatch):
    """An architecture module that exists only in a temporary directory,
    named by the tiny configuration's ``architecture.kind``."""
    import bench.arch
    import bench.model

    kind = "files_only_arch"
    arch_dir = tmp_path / "arch"
    arch_dir.mkdir()
    (arch_dir / f"{kind}.py").write_text(NEW_ARCH)
    monkeypatch.setattr(bench.arch, "__path__",
                        [*bench.arch.__path__, str(arch_dir)])
    configs = bench.model.CONFIG_DIR
    raw = json.loads((configs / "tiny.json").read_text())
    raw["architecture"]["kind"] = kind
    (configs / "tiny.json").write_text(json.dumps(raw))
    bench_json = json.loads(tiny_cell.read_text())
    bench_json["per_layer"].append({
        "name": "mfu", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "model step",
        "moves": "output_tok_s"})
    tiny_cell.write_text(json.dumps(bench_json))
    yield kind
    sys.modules.pop(f"bench.arch.{kind}", None)


def test_a_new_architecture_joins_as_files_only(new_arch, monkeypatch):
    import bench.peaks
    from bench.harness import run

    spec = ModelSpec.load("tiny")
    calls = spec.arch.CALLS
    assert spec.arch.__name__ == f"bench.arch.{new_arch}"
    assert [n for n, _, _ in spec.shape.projections()] == ["q", "k", "v", "o"]
    w = spec.make_weights(seed_key(3))
    assert w["layers"]["pos0"]["mlp"]["w_gate"].shape == (2, 64, 128)
    assert spec.shape.request_model_flops(16, 3) > 0
    # a peak, so that mfu reads through the new shape's count
    monkeypatch.setattr(bench.peaks, "peak_for",
                        lambda kind: bench.peaks.PEAKS["TPU v5 lite"])
    before = dict(calls)
    result = run("tiny.mix", 2 ** 31 + 5, 0.3, True,
                 t_process=time.perf_counter())
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["mfu"]["value"] > 0
    for name in calls:
        assert calls[name] > before[name], (name, calls)
