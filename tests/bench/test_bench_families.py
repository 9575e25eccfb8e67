"""The model family behind each configuration: found by name, data root
first; a family with no module fails at set-up with the path it looked
for; and the dense family's counts and reference readings are exactly the
numbers the harness gave before they moved into ``bench/families``."""
import pathlib
import shutil

import jax
import pytest

from bench import run
from bench.lib import flops
from bench.lib.peaks import peaks_for
from bench.lib.reference import ReferenceTrainer
from bench.lib.spec import Spec
from bench.lib.train_loop import make_batches

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"

# train_flops_per_step and the summed least time of dense_unit_grids on a
# v5e, as bench/lib/flops.py computed them before the family modules
COUNTS = {
    "qwen1.5-0.5b.train_16x1k": (48075984863232, 0.08087920911165426),
    "h2o-danube3-4b.pp6.train_1x4k": (19786754949120, 0.03880270470589158),
}
# the plain reference at tiny-dense.train_tiny, seed 2 ** 31 + 7, as
# bench/lib/reference.py computed it before: losses, and (first gradient,
# change after three steps) of three leaves
SEED = 2 ** 31 + 7
LOSSES = [6.086357116699219, 6.070694923400879, 5.995913028717041]
LEAVES = {
    "blocks/attn/wq/0": (0.7046679258346558, 0.1273414045572281),
    "blocks/mlp/w_down/1": (0.5404303669929504, 0.0983823612332344),
    "embed": (1.6250602006912231, 0.29725193977355957),
}


@pytest.mark.parametrize("workload", sorted(COUNTS))
def test_counts_equal_the_harness_before_the_move(workload):
    spec = Spec(ROOT / "BENCHMARK.json")
    w = spec.workload(workload)
    m, t = spec.config(w["config"])["model"], spec.traffic(w["traffic"])
    fam = spec.family(m)
    peaks = peaks_for("TPU v5 lite")
    least = flops.least_time_s(
        fam.dense_unit_grids(m, t["batch"] * t["seq"]),
        peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"])
    assert (fam.train_flops_per_step(m, t["batch"], t["seq"]), least) == \
        COUNTS[workload]


def test_reference_readings_equal_the_harness_before_the_move():
    spec = Spec(DATA / "BENCHMARK.json")
    w = spec.workload("tiny-dense.train_tiny")
    m, t = spec.config(w["config"])["model"], spec.traffic(w["traffic"])
    with jax.default_matmul_precision("highest"):
        got = ReferenceTrainer(spec.family(m), m, t["lr"],
                               t["optimizer"]["momentum"]).run(
            SEED, make_batches(m, t, SEED)[:t["check_steps"]])
    assert got["losses"] == LOSSES
    for leaf, (grad, change) in LEAVES.items():
        assert (got["grad_norms"][leaf], got["change_norms"][leaf]) == \
            (grad, change), leaf


def test_family_in_the_data_root_is_found_first():
    m = {"family": "dense"}
    assert Spec(DATA / "BENCHMARK.json").family(m).FOUND_IN == \
        "tests/bench/data"
    checkout = Spec(ROOT / "BENCHMARK.json").family(m)
    assert not hasattr(checkout, "FOUND_IN")
    assert callable(checkout.layer_forward)


def test_family_without_a_module_fails_at_setup_naming_the_path(
        tmp_path, monkeypatch):
    """The tiny MLA + MoE config, in a data root and a checkout that hold
    no family module, whatever modules this checkout gains later."""
    from bench.lib import spec as S
    from repro.models.config import ModelConfig
    for rel in ("BENCHMARK.json", "bench/configs/tiny-mla-moe.json"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(DATA / rel, tmp_path / rel)
    monkeypatch.setattr(S, "ROOT", tmp_path / "checkout")
    spec = Spec(tmp_path / "BENCHMARK.json")
    m = spec.config("tiny-mla-moe")["model"]
    ModelConfig(**m)                    # the program takes the config
    args = run.parse(["--workload", "tiny-mla-moe.train_tiny", "--seed",
                      str(SEED), "--seconds", "0.5", "--trace", "0",
                      "--spec", str(tmp_path / "BENCHMARK.json")])
    with pytest.raises(FileNotFoundError, match="bench/families/moe.py"):
        run.run_cell(args, require_tpu=False)
