"""``correct`` fails where it must, at a size a test run holds.

The control (the plain reference with 4-bit dense units put in the
program's place) fails the cell's limits; and a run driven through the
harness with the timed path broken underneath reads ``correct`` false for
each fault the cells can have: a step that returns its state unchanged,
and half of the batch left out with the mean taken over the rest.  (One
chip: no exchange between chips to leave out.)"""
import pathlib

import jax
import pytest

from bench import run
from bench.lib import compare, train_loop
from bench.lib.spec import Spec

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2 ** 32 + 17


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def run_cell(workload):
    args = run.parse(["--workload", workload, "--seed", str(SEED),
                      "--seconds", "0.5", "--trace", "0",
                      "--spec", str(DATA / "BENCHMARK.json")])
    return run.run_cell(args, require_tpu=False)


def cell_parts(workload):
    spec = Spec(DATA / "BENCHMARK.json")
    w = spec.workload(workload)
    return (spec.config(w["config"])["model"], spec.traffic(w["traffic"]),
            spec.limits(workload))


def test_train_control_fails_the_limits():
    m, traffic, limits = cell_parts("tiny-dense.train_tiny")
    r = train_loop.TrainRun(m, traffic, SEED, print,
                            Spec(DATA / "BENCHMARK.json").family(m))
    r.setup()
    nums = compare.train_numbers(r.reference(bits=4), r.reference())
    ok, checks = compare.judge(nums, limits)
    assert not ok, checks


def broken_step(fault):
    real = train_loop.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def run_step(params, opt, batch, hyper, bits, rng=None):
            if fault == "half_batch":
                b = batch["tokens"].shape[0] // 2
                batch = {k: v[:b] for k, v in batch.items()}
                return step(params, opt, batch, hyper, bits, rng)
            _, _, met = step(params, opt, batch, hyper, bits, rng)
            return params, opt, met
        return run_step
    return make


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_faults_read_incorrect(monkeypatch, fault):
    monkeypatch.setattr(train_loop, "make_train_step", broken_step(fault))
    out = run_cell("tiny-dense.train_tiny")
    assert out["correct"] is False, out["checks"]
