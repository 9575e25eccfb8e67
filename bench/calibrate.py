"""Readings that a training cell's correctness limits are set from, on the
chip.  The benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9

For each seed the program's checked steps against the plain reference;
for each control seed also the control (the reference with 4-bit dense
units in the program's place) and the half-batch fault (the reference on
half of each batch, the mean over the rest), each against the reference.  A step that returns its state unchanged reads 1 in
the change by construction and needs no run.

One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import check_devices, enable_cache, log

from bench.lib.compare import train_numbers
from bench.lib.spec import Spec
from bench.lib.train_loop import TrainRun


def emit(**kw):
    print(json.dumps(kw), flush=True)


def train(m, traffic, seeds, controls, family):
    run = None
    for seed in dict.fromkeys(seeds + controls):
        t0 = time.perf_counter()
        if run is None:
            run = TrainRun(m, traffic, seed, log, family)
            run.setup()
        else:
            run.start(seed)
        prog = run.readings
        run.free()
        ref = run.reference()
        readings = []
        if seed in seeds:
            readings.append(("program", prog))
        if seed in controls:
            readings += [("control_int4", run.reference(bits=4)),
                         ("fault_half_batch", run.reference(half=True))]
        for kind, r in readings:
            nums = train_numbers(r, ref)
            emit(kind=kind, seed=seed, seconds=time.perf_counter() - t0,
                 **{k: v["value"] for k, v in nums.items()},
                 leaves={k: v.get("leaf") for k, v in nums.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--spec", default=None,
                    help="BENCHMARK.json to read (default: the checkout's)")
    args = ap.parse_args(argv)
    spec = Spec(args.spec)
    cell = spec.workload(args.workload)
    check_devices(cell["chips"])
    enable_cache()
    m = spec.config(cell["config"])["model"]
    traffic = spec.traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    train(m, traffic, seeds, controls, spec.family(m))
    return 0


if __name__ == "__main__":
    sys.exit(main())
