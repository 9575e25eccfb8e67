"""Benchmark entry: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout on a machine that holds the chips the
cell asks for, and fails (exit 3, no result) where JAX finds no TPU or too
few chips.  The cell's configuration, traffic mix, limits and per-layer
metric readers are found by name from ``BENCHMARK.json``
(``bench/lib/spec.py``).  Set-up (weights from the seed, compiles or cache
loads, every shape of the window, the first checked steps) is timed from
process start to the first timed step as ``setup_s``.  The window then
runs for ``--seconds``.  After it, the peak device memory is read, the
program's state is freed and the plain reference decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit.  The same checks are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = ROOT / ".bench_cache"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=None,
                    help="BENCHMARK.json to read (default: the checkout's)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the profiler trace here and keep it")
    return ap.parse_args(argv)


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return devs[:chips]


def device_info(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def enable_cache():
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make_run(kind: str, m: dict, traffic: dict, seed: int, family):
    if kind == "train":
        from bench.lib import train_loop
        return train_loop, train_loop.TrainRun(m, traffic, seed, log, family)
    raise ValueError(f"unknown traffic kind {kind!r}")


def run_cell(args, require_tpu: bool = True) -> dict:
    import jax
    from bench.lib import compare
    from bench.lib import trace_reader as TR
    from bench.lib.peaks import peaks_for
    from bench.lib.spec import Spec
    from bench.lib.stats import CompileStats

    spec = Spec(args.spec)
    cell = spec.workload(args.workload)
    m = spec.config(cell["config"])["model"]
    family = spec.family(m)
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(args.workload)
    if require_tpu:
        devs = check_devices(cell["chips"])
        enable_cache()
    else:
        devs = jax.devices()[:cell["chips"]]
    peaks = peaks_for(devs[0].device_kind) if require_tpu else None
    stats = CompileStats()
    mod, run = make_run(traffic["kind"], m, traffic, args.seed, family)
    run.setup()
    setup_s = time.perf_counter() - T_START
    before = stats.snapshot()
    log(f"setup {setup_s:.3f} s; compiles so far {before}")

    if args.trace:
        trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                result = run.window(args.seconds, jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
    else:
        result = run.window(args.seconds)
    after = stats.close()
    in_window = after["compiles"] - before["compiles"]
    log("window: " + json.dumps({k: v for k, v in result.items()
                                 if not isinstance(v, list)}))
    log(f"compiles inside the window: {in_window} "
        f"({after['compile_s'] - before['compile_s']:.3f} s)")
    device = device_info(devs)

    record = {"model": m, "run": run, "traffic": traffic, "peaks": peaks,
              "result": result, "window_s": result["window_s"],
              "flops": mod.window_flops(run, result), "trace": None}
    breakdown = None
    if args.trace:
        trace = TR.load_xplane(TR.find_xplane(trace_dir))
        win = TR.window_of(trace, "bench.window")
        record.update(trace=trace, trace_window=win)
        device["busy_s"] = TR.busy_s(trace, win)
        device["window_s"] = (win[1] - win[0]) / 1e9
        breakdown = {"device_ops": TR.top_ops(trace, win),
                     "idle_gaps": TR.idle_gaps(trace, win)}
        if args.keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    if args.trace:
        for entry in spec.per_layer(args.workload):
            value = spec.reader(entry["name"])(record)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        values = dict(result.get("metrics", {}), setup_s=setup_s)
        for entry in spec.end_to_end(args.workload):
            metrics[entry["name"]] = {"value": values[entry["name"]],
                                      "unit": entry["unit"]}

    run.free()
    t_ref = time.perf_counter()
    numbers = mod.numbers(run)
    correct, checks = compare.judge(numbers, limits)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s; "
        f"numbers {json.dumps(numbers)}")
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run_cell(args)
    except NoChip as e:
        log(f"refused: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
