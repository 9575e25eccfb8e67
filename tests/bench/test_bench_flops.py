"""The FLOP and byte counters, against the program's own parameter count
for every configuration of the benchmark, each through its family's
module; and the dense family's counts at fixed sizes."""
import dataclasses
import json
import math
import pathlib

import jax
import pytest

from bench.families import dense
from bench.lib import flops
from bench.lib import weights as W
from bench.lib.reference import leaf_items
from bench.lib.spec import Spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = [c["file"] for c in SPEC["configs"]]


def model(path):
    return json.loads((ROOT / path).read_text())["model"]


def family(m):
    return Spec(ROOT / "BENCHMARK.json").family(m)


@pytest.mark.parametrize("path", CONFIGS)
def test_param_count_matches_model_config(path):
    from repro.models.config import ModelConfig
    m = model(path)
    assert family(m).param_count(m) == ModelConfig(**m).param_count()


def _not_matmul(name):
    """Norm scales and biases, by the program's names for them."""
    parts = name.split("/")
    return (any(p.endswith("norm") for p in parts)
            or parts[-1] in ("bq", "bk", "bv", "bias"))


@pytest.mark.parametrize("path", CONFIGS)
def test_matmul_params_exclude_norms_and_biases(path):
    """The weights a token multiplies: the program's active parameters
    less the family's norm scales and biases (counted from the weights it
    makes), and less an untied input table, which is a lookup; the head
    counts once."""
    from repro.models.config import ModelConfig
    m = model(path)
    fam = family(m)
    tree = jax.eval_shape(lambda: W.stacked_params(W.seed_key(0), m, fam))
    extra = sum(math.prod(a.shape) for name, a in leaf_items(tree)
                if _not_matmul(name))
    if "lm_head" in tree:
        extra += math.prod(tree["embed"].shape)
    assert fam.matmul_params(m) == \
        ModelConfig(**m).active_param_count() - extra


def test_qwen_flops_per_trained_token():
    m = model("bench/configs/qwen1.5-0.5b.json")
    per_tok = family(m).train_flops_per_step(m, 16, 1024) / (16 * 1024)
    assert per_tok == pytest.approx(2.934e9, rel=1e-3)


def test_keys_attended_causal_and_windowed():
    assert flops.keys_attended(0, 4) == 1 + 2 + 3 + 4
    assert flops.keys_attended(2, 2) == 3 + 4
    assert flops.keys_attended(0, 5, window=2) == 1 + 2 + 2 + 2 + 2


def test_dense_unit_grids_and_least_time():
    m = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "d_ff": 16,
         "vocab_size": 32, "num_layers": 2}
    grids = dense.dense_unit_grids(m, rows=4)
    assert len(grids) == 2 * 7 * 3
    assert grids[:3] == [(4, 8, 8), (4, 8, 8), (8, 8, 4)]
    # one grid, compute-bound vs bandwidth-bound
    g = [(128, 128, 128)]
    assert flops.least_time_s(g, 1.0, 1e30) == pytest.approx(2 * 128 ** 3)
    assert flops.least_time_s(g, 1e30, 1.0) == pytest.approx(
        128 * 128 * 2 + 4 * 128 * 128)


def test_peaks_table_refuses_unknown_devices():
    from bench.lib.peaks import peaks_for
    p = peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_config_files_are_model_configs():
    from repro.models.config import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    for path in CONFIGS:
        assert set(model(path)) <= names
