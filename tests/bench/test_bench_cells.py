"""A cell defined outside ``bench/``: a configuration, a traffic mix, its
limits and a per-layer metric, all added as files and entries in
``tests/bench/data``, run through the harness on the CPU (the look for a
chip skipped) and come out correct."""
import pathlib

import jax
import pytest

from bench import run

DATA = pathlib.Path(__file__).resolve().parent / "data"


def run_cell(workload, trace=0, seed=2 ** 31 + 99):
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", str(trace),
                      "--spec", str(DATA / "BENCHMARK.json")])
    with jax.default_matmul_precision("highest"):
        return run.run_cell(args, require_tpu=False)


def test_train_cell_from_data_files():
    out = run_cell("tiny-dense.train_tiny")
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reads_the_data_dir_metric():
    out = run_cell("tiny-dense.train_tiny", trace=1)
    # the test-only reader is found by name; the device readers find no
    # device plane on the CPU and are left out
    assert out["metrics"]["steps_in_window.test"]["value"] >= 1
    assert "train_step.mfu" not in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        run_cell("tiny-dense.nothing")
