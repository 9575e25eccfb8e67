"""Serving driver: continuous-batching decode over the slot scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --requests 8 --max-new 16 [--mode paged|contiguous]

Demonstrates the production serving path behind the PR-8 API: a
``ServeConfig`` + ``EngineHooks.for_model`` pair drives either the paged
block-pool scheduler (chunked prefill, prefix sharing, COW) or the legacy
contiguous per-slot cache, with per-request latency accounting.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.kernels import ops as kops
from repro.launch.train import _reduce
from repro.models import lm
from repro.serving import (BatchScheduler, EngineHooks, Request, ServeConfig,
                           paged_supported)
from repro.util.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the request prompts")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "paged", "contiguous"],
                    help="auto: paged for the GQA-KV families, contiguous "
                         "otherwise (MLA/SWA/SSM/hybrid/encdec)")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill token budget per tick (paged mode; "
                         "default: block size)")
    ap.add_argument("--cache-dtype", default=None,
                    choices=["bfloat16", "float32", "int8"],
                    help="KV storage dtype (default: the compute dtype)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop at this token id (default: run to max-new)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["auto", "off", "emulate", "int8"],
                    help="decode-hook kernel backend: non-off enables the "
                         "fused decode-prologue and paged-attention kernels "
                         "(default: unset, unfused decode)")
    ap.add_argument("--profile", type=int, default=0, metavar="N",
                    help="capture a jax.profiler trace of the first N "
                         "scheduler ticks (trace directory printed at exit)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = _reduce(cfg)
    params = lm.init_params(jax.random.key(args.seed), cfg)

    mode = args.mode
    if mode == "auto":
        mode = "paged" if paged_supported(cfg) else "contiguous"
    cache_dtype = args.cache_dtype or (
        "bfloat16" if cfg.compute_dtype == "bfloat16" else "float32")
    serve = ServeConfig(num_slots=args.slots, eos_id=args.eos_id,
                        max_len=args.max_len, mode=mode,
                        block_size=args.block_size,
                        prefill_chunk=args.prefill_chunk,
                        cache_dtype=cache_dtype,
                        kernel_backend=args.kernel_backend,
                        attn_impl=("kernel" if args.kernel_backend
                                   not in (None, "off") else None))
    print(f"[serve] {cfg.name} ({cfg.family}) slots={args.slots} "
          f"mode={mode} cache={cache_dtype} "
          f"kernel_backend={args.kernel_backend or 'unset'}", flush=True)

    if mode == "paged":
        # prime the kernel tune cache for this serve's decode shapes (paged
        # attention + fused prologue) so the first decode tick traces
        # against stable decisions instead of deriving them mid-trace
        from repro.kernels.ops import prime_tune_cache, serve_tune_shapes
        tuned = prime_tune_cache(serve_tune_shapes(
            cfg, num_blocks=serve.resolved_num_blocks,
            block_size=serve.block_size,
            max_blocks_per_seq=serve.max_blocks_per_seq))
        hits = sum(1 for d in tuned.values() if d is not None)
        print(f"[serve] kernel tune cache primed: {hits}/{len(tuned)} "
              f"shape(s) fit VMEM", flush=True)

    sched = BatchScheduler(serve, EngineHooks.for_model(params, cfg, serve))

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    reqs = []
    for i in range(args.requests):
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=(args.prompt_len,)).astype(np.int32),
            max_new_tokens=args.max_new))
        sched.submit(reqs[-1])
    trace_dir = None
    if args.profile > 0:
        trace_dir = tempfile.mkdtemp(prefix="repro-trace-serve-")
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(args.profile):
                if sched.step() == 0 and not sched.pending:
                    break
        finally:
            jax.profiler.stop_trace()
    sched.run_until_drained()
    finished = [r for r in reqs if r.done]
    dt = time.time() - t0
    tok = sum(len(r.generated) for r in finished)
    extra = ""
    if mode == "paged":
        extra = (f", {sched.stats['prefix_hits']} prefix hits, "
                 f"{sched.stats['cow_copies']} COW copies")
    print(f"[serve] {len(finished)}/{args.requests} requests, {tok} tokens "
          f"in {dt:.1f}s ({tok/dt:.1f} tok/s, {sched.steps_run} decode steps"
          f"{extra})", flush=True)
    for r in finished[:3]:
        print(f"  req {r.uid}: {r.generated[:8]}...", flush=True)
    if trace_dir:
        print(f"[serve] profiler trace ({args.profile} tick(s)): {trace_dir}",
              flush=True)
    print(f"[serve] kernel paths: {kops.format_kernel_traces()}", flush=True)
    return {"finished": finished, "ticks": sched.steps_run, "seconds": dt}


if __name__ == "__main__":
    main()
