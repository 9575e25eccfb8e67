"""``bench/calibrate.py`` drives the harness's ``TrainRun`` through a data
root's cell on the CPU (its look for a chip skipped): the program's
readings pass the cell's limits, and the control's and the half-batch
fault's fail them."""
import importlib
import json
import pathlib

import jax

from bench.lib.spec import Spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
WORKLOAD = "tiny-dense.train_tiny"
SEEDS = (2 ** 31 + 21, 2 ** 31 + 22)


def test_calibrate_reads_program_control_and_fault(monkeypatch, capsys):
    monkeypatch.syspath_prepend(str(ROOT / "bench"))
    calibrate = importlib.import_module("calibrate")
    monkeypatch.setattr(calibrate, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(calibrate, "enable_cache", lambda: None)
    with jax.default_matmul_precision("highest"):
        assert calibrate.main([
            "--workload", WORKLOAD, "--seeds", ",".join(map(str, SEEDS)),
            "--control-seeds", str(SEEDS[1]),
            "--spec", str(DATA / "BENCHMARK.json")]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert sorted((ln["kind"], ln["seed"]) for ln in lines) == sorted(
        [("program", SEEDS[0]), ("program", SEEDS[1]),
         ("control_int4", SEEDS[1]), ("fault_half_batch", SEEDS[1])])
    limits = Spec(DATA / "BENCHMARK.json").limits(WORKLOAD)["limits"]
    for ln in lines:
        over = [k for k, lim in limits.items() if not ln[k] <= lim]
        assert bool(over) == (ln["kind"] != "program"), (ln, over)
