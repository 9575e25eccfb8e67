"""Chip smoke test: the repo's main paths, once each, on a TPU.

    python chip_smoke.py            # one chip: train, serve and paper phases
    python chip_smoke.py --chips 4  # the 2x2-mesh train path vs one device

Phases, in order, in this one process (one process holds the chip):

* train: ``repro.launch.train`` on qwen1.5-0.5b at full width, default
  (int8) kernel datapath, seq 1024 x global batch 8, 6 steps.  Every loss
  is finite, the mean of the last two is below step 0's, and every dense
  unit of the step ran as a compiled Mosaic kernel.
* serve: ``repro.launch.serve`` on qwen1.5-0.5b at full width, paged KV,
  8 slots, 16 requests of 256 prompt tokens, 32 new tokens each, decode on
  the int8 kernels.  Every request finishes with its 32 in-vocabulary
  tokens.
* paper: 25 steps of the LeNet-class train step on the int8 kernels (the
  one caller of the fused TDM frame ``bp_fused_unit``) against the float
  oracle's first step: loss within 5%, parameters within 0.05, and the
  25th loss below 0.8x the first.

``--chips 4`` runs only the train driver on a data=2 x model=2 mesh for 4
steps and the same first step on a one-device mesh; step-0 losses agree
within 1e-3 relative.

Each phase prints one JSON line: compile seconds (JAX's backend compile
events, persistent-cache reads included), cache hits and misses, step or
tick seconds, the device's ``peak_bytes_in_use`` so far, and which path
each kernel call site took.  No kernel may run in interpret mode.  The
last line is ``{"ok": true, "device": {...}}``; on any failure it is
``{"ok": false, ...}`` and the exit code is 1.  Weights and data are made
from ``--seed``.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen1.5-0.5b"
DENSE_UNITS = ("dense_fwd", "dense_bwd_dx", "dense_bwd_dw")


class PhaseStats:
    """Compile events and kernel paths counted while one phase runs."""

    def __init__(self):
        import jax
        from repro.kernels import ops as kops
        self._kops = kops
        self.compile_s = 0.0
        self.events = collections.Counter()
        self._paths0 = collections.Counter(kops.KERNEL_TRACES)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def close(self) -> dict:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        paths = self._kops.KERNEL_TRACES - self._paths0
        stats = jax.devices()[0].memory_stats() or {}
        return {"compile_s": self.compile_s,
                "cache_hits": self.events["cache_hits"],
                "cache_misses": self.events["cache_misses"],
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "kernel_paths": {f"{k}/{p}": n
                                 for (k, p), n in sorted(paths.items())}}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_no_interpret(paths: dict) -> None:
    interp = [k for k in paths if k.endswith("/interpret")]
    check(not interp, f"kernels ran in interpret mode: {interp}")


def check_dense_compiled(paths: dict) -> None:
    for unit in DENSE_UNITS:
        check(paths.get(f"{unit}/compiled", 0) > 0,
              f"{unit} never ran as a compiled kernel: {paths}")
    fell = [k for k in paths
            if k.split("/")[0] in DENSE_UNITS and not k.endswith("/compiled")]
    check(not fell, f"dense units fell back: {fell}")


def train_args(seed: int, steps: int, extra=()) -> list:
    return ["--arch", ARCH, "--kernel-backend", "auto", "--seq-len", "1024",
            "--global-batch", "8", "--steps", str(steps), "--lr", "0.3",
            "--log-every", "1", "--seed", str(seed), *extra]


def phase_train(seed: int) -> dict:
    import numpy as np
    from repro.launch import train
    stats = PhaseStats()
    run = train.main(train_args(seed, 6))
    out = stats.close()
    losses, secs = run["losses"], run["step_seconds"]
    out.update(losses=losses, first_step_s=secs[0],
               step_s_median=float(np.median(secs[1:])))
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    check(np.mean(losses[-2:]) < losses[0],
          f"loss did not descend: {losses}")
    check_no_interpret(out["kernel_paths"])
    check_dense_compiled(out["kernel_paths"])
    return out


def phase_serve(seed: int) -> dict:
    from repro.configs import get_config
    from repro.launch import serve
    stats = PhaseStats()
    max_new, requests = 32, 16
    run = serve.main(["--arch", ARCH, "--mode", "paged", "--slots", "8",
                      "--requests", str(requests), "--prompt-len", "256",
                      "--max-new", str(max_new), "--max-len", "1024",
                      "--kernel-backend", "auto", "--seed", str(seed)])
    out = stats.close()
    finished = run["finished"]
    out.update(requests_finished=len(finished), ticks=run["ticks"],
               serve_wall_s=run["seconds"],
               tick_s_mean_incl_compile=run["seconds"] / max(run["ticks"], 1))
    vocab = get_config(ARCH).vocab_size
    check(len(finished) == requests,
          f"{len(finished)}/{requests} requests finished")
    for r in finished:
        check(len(r.generated) == max_new,
              f"request {r.uid}: {len(r.generated)} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"request {r.uid}: token out of vocabulary")
    check_no_interpret(out["kernel_paths"])
    return out


def phase_paper(seed: int) -> dict:
    import jax
    import numpy as np
    from repro.configs.lenet5 import LeNetConfig
    from repro.core.lenet import (init_lenet_params, lenet_bits,
                                  make_lenet_train_step)
    cfg, bsz = LeNetConfig(), 64
    stats = PhaseStats()
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (bsz, cfg.input_dim))
    y = jax.random.randint(jax.random.fold_in(key, 2), (bsz,), 0,
                           cfg.num_classes)
    params = init_lenet_params(jax.random.fold_in(key, 0), cfg)
    bits = lenet_bits(cfg.num_layers)
    with jax.default_matmul_precision("highest"):
        oracle = jax.jit(make_lenet_train_step(cfg, bits, "off"))
        int8 = jax.jit(make_lenet_train_step(cfg, bits, "int8"))
        p_off, m_off = oracle(params, (x, y), 0.1)
        p_i8, m_i8 = int8(params, (x, y), 0.1)
        diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                   for a, b in zip(jax.tree.leaves(p_i8),
                                   jax.tree.leaves(p_off)))
        losses, secs, p = [], [], params
        for _ in range(25):
            t = time.time()
            p, m = int8(p, (x, y), 0.2)
            losses.append(float(m["loss"]))
            secs.append(time.time() - t)
    out = stats.close()
    loss_off, loss_i8 = float(m_off["loss"]), float(m_i8["loss"])
    out.update(loss_oracle=loss_off, loss_int8=loss_i8,
               param_max_diff=diff, losses=[losses[0], losses[-1]],
               step_s_median=float(np.median(secs[1:])))
    check(abs(loss_i8 - loss_off) <= 0.05 * abs(loss_off),
          f"int8 loss {loss_i8} vs oracle {loss_off}")
    check(diff < 0.05, f"int8 params differ from the oracle by {diff}")
    check(bool(np.isfinite(losses).all()) and losses[-1] < 0.8 * losses[0],
          f"int8 LeNet did not train: {losses}")
    check_no_interpret(out["kernel_paths"])
    check(out["kernel_paths"].get("bp_fused_unit/compiled", 0) > 0,
          f"the fused TDM frame did not run: {out['kernel_paths']}")
    return out


def phase_mesh(seed: int) -> dict:
    """The 2x2-mesh train driver vs its first step on one device."""
    from repro.launch import train
    stats = PhaseStats()
    mesh = train.main(train_args(seed, 4, ("--data", "2", "--model", "2")))
    one = train.main(train_args(seed, 1, ("--data", "1", "--model", "1")))
    out = stats.close()
    l4, l1 = mesh["losses"][0], one["losses"][0]
    out.update(losses_mesh=mesh["losses"], loss0_one_device=l1,
               rel_diff=abs(l4 - l1) / abs(l1),
               first_step_s=mesh["step_seconds"][0])
    check(abs(l4 - l1) <= 1e-3 * abs(l1),
          f"step-0 loss on the mesh {l4} vs one device {l1}")
    check_no_interpret(out["kernel_paths"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.util.compile_cache import enable_compile_cache
        devices = jax.devices()
        platform = devices[0].platform
        check(platform == "tpu", f"no TPU: JAX found {platform}")
        check(len(devices) >= args.chips,
              f"{args.chips} chips asked, {len(devices)} found")
        print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
        if args.chips == 4:
            phases = [("mesh", phase_mesh)]
        else:
            phases = [("train", phase_train), ("serve", phase_serve),
                      ("paper", phase_paper)]
        for name, fn in phases:
            print(f"[smoke] phase {name}", flush=True)
            out = fn(args.seed)
            print(json.dumps({"phase": name, **out}), flush=True)
    except Exception as e:  # noqa: BLE001 - any failure fails the run
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {"platform": d[0].platform,
                                             "kind": d[0].device_kind,
                                             "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
