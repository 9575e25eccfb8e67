"""Production training driver: elastic mesh, checkpoint/restart, straggler-
tolerant data loading, fault-injection drills, TaxoNN engine.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 200 --reduced --ckpt-dir /tmp/run1 [--resume]

Elasticity: the mesh is built from whatever devices exist at START-UP
(``--data X --model Y`` or auto); checkpoints store logical arrays, so a
job checkpointed on one topology restarts on another (restore reshards via
the new mesh's shardings).  Fault tolerance: atomic verified async
checkpoints every ``--ckpt-every`` steps carrying the full resume payload
(data step, transport-cache decisions — see
``core.steps.capture_resume_extra``); on restart the step-indexed data
pipeline resumes exactly and a same-topology restart is BITWISE identical
to the uninterrupted run.  ``--fault-plan`` (or ``REPRO_FAULT_PLAN``)
injects deterministic faults — crash-at-step, checkpoint IO/fsync/rename
failures, straggler stalls, post-save bit flips — for reproducible
recovery drills (see ``repro.ft``); a restart past a corrupted LATEST
falls back to the newest valid checkpoint with a loud warning.
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import AsyncCheckpointer, restore_checkpoint, latest_step
from repro.configs import ARCH_NAMES, get_config
from repro.core import QuantPolicy, StepOptions, make_train_step
from repro.core.steps import (apply_resume_extra, capture_resume_extra,
                              default_bits, init_train_state)
from repro.data import SyntheticLMDataset, StragglerTolerantLoader
from repro.dist.api import activation_sharding_ctx, make_default_rules
from repro.dist.pipeline import get_schedule
from repro.dist.sharding import batch_pspecs, opt_pspecs, param_pspecs, to_named
from repro.ft import FaultPlan
from repro.kernels import ops as kops
from repro.launch.mesh import batch_axes, make_debug_mesh, pipe_axis_size
from repro.models import lm
from repro.optim import Hyper, OptimizerConfig, cosine_schedule
from repro.util.compile_cache import enable_compile_cache


def _reduce(cfg):
    """Small same-family twin for CPU runs (--reduced)."""
    changes = dict(num_layers=min(cfg.num_layers, 4), d_model=128,
                   vocab_size=512, compute_dtype="float32")
    if cfg.num_heads:
        kv = cfg.num_kv_heads if cfg.num_kv_heads == cfg.num_heads else 2
        changes.update(num_heads=4, num_kv_heads=min(kv, 4), head_dim=32)
    if cfg.d_ff:
        changes.update(d_ff=256)
    if cfg.family == "moe":
        changes.update(num_experts=4, experts_per_token=2, moe_d_ff=64)
    if cfg.use_mla:
        changes.update(kv_lora_rank=32, qk_nope_dim=32, qk_rope_dim=16,
                       v_head_dim=32)
    if cfg.family in ("ssm", "hybrid"):
        changes.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
    if cfg.family == "hybrid":
        changes.update(num_layers=4, attn_every=2)
    if cfg.family == "encdec":
        changes.update(num_encoder_layers=2, encoder_seq=32)
    if cfg.family == "vlm":
        changes.update(num_patches=8)
    return dataclasses.replace(cfg, **changes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the initial weights and the synthetic data")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="momentum",
                    choices=["sgd", "momentum", "momentum8", "adam"])
    ap.add_argument("--quantize", action="store_true",
                    help="enable the TaxoNN per-layer (I,F) schedule")
    ap.add_argument("--bit-anneal", default=None, metavar="SPEC",
                    help="progressive bitwidth-annealing schedule, e.g. "
                         "'0:off,100:16,400:12': comma-separated STEP:VALUE "
                         "milestones where VALUE is an F-bit floor applied "
                         "on top of the per-layer schedule ('off' = "
                         "quantization disabled until the next milestone); "
                         "bits stay traced data so the ramp costs zero "
                         "recompiles and resume continues it bitwise (see "
                         "repro.search.anneal)")
    ap.add_argument("--bit-search", type=int, default=0, metavar="GROUPS",
                    help="run a per-layer-group (I,F) sensitivity sweep on "
                         "this arch before training (GROUPS contiguous "
                         "layer groups; 0 = off) and train with the "
                         "selected plan; the BitPlan + its serving int8 "
                         "export are saved next to the checkpoints (or "
                         "under artifacts/)")
    ap.add_argument("--bit-target", type=float, default=0.1,
                    help="--bit-search loss-delta target vs the f32 "
                         "baseline probe")
    ap.add_argument("--bit-probe-steps", type=int, default=24,
                    help="--bit-search training steps per probe")
    ap.add_argument("--engine", default="taxonn",
                    choices=["taxonn", "autodiff"])
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "off", "emulate", "int8"],
                    help="dense-unit datapath (auto = off on CPU, int8 on "
                         "TPU)")
    ap.add_argument("--compress-dw", action="store_true",
                    help="route per-layer dW through the int8 block-scaled "
                         "wire format inside the backward scan")
    ap.add_argument("--stochastic", action="store_true",
                    help="stochastic rounding for the quantized G chain "
                         "(and updates with --quantize-updates); noise is "
                         "keyed per (layer, global batch row), so the scan "
                         "and pipeline paths make identical draws")
    ap.add_argument("--quantize-updates", action="store_true",
                    help="strict paper mode: quantize q(alpha*dW) in the "
                         "layer's gradient (I,F) format before the update")
    ap.add_argument("--overlap", default="off", choices=["off", "on"],
                    help="comm-optimized backward scan: ring-transport dW "
                         "leaves software-pipeline --overlap-depth scan "
                         "steps deep so the in-flight hops overlap the "
                         "next layers' G-step compute, blocking-transport "
                         "leaves land same-iteration updates (fused psum, "
                         "or the sharded sgd update on scatter leaves); "
                         "each bucket's transport comes from the per-size "
                         "autotuner unless --transport forces one")
    ap.add_argument("--overlap-depth", type=int, default=2,
                    help="in-flight dW reduces per layer stream with "
                         "--overlap on (clamped to the layer count; only "
                         "ring-transport leaves defer)")
    ap.add_argument("--transport", default="auto",
                    choices=["auto", "ring", "psum", "scatter"],
                    help="dW all-reduce transport: auto consults the "
                         "measured per-bucket cache (primed at start-up "
                         "for this model's dW sizes; REPRO_TRANSPORT "
                         "overrides everything); ring/psum/scatter force "
                         "one (scatter = native reduce-scatter whose 1/g "
                         "chunk gets the sharded optimizer update)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale reduced twin of the arch")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault-injection spec for recovery "
                         "drills (falls back to REPRO_FAULT_PLAN), e.g. "
                         "'crash@12;io@8x2;stall@5:0.5;flip@10;seed=7' or "
                         "'crash@rand:8-20;seed=3' — see repro.ft.FaultPlan")
    ap.add_argument("--data", type=int, default=0,
                    help="data-axis size (0 = all devices)")
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipe-axis size (0 = no pipe axis in the mesh)")
    ap.add_argument("--pipeline-schedule", default="none",
                    choices=["none", "gpipe", "1f1b", "interleaved"],
                    help="pipe-axis pipeline schedule; with stages > 1 the "
                         "engine's blocks stack EXECUTES stage-sharded "
                         "through repro.dist.pipeline for EVERY model "
                         "family (hybrid/encdec shared operands replicate "
                         "or slice per stage, moe aux statistics reduce "
                         "post-drain; layers and batch must divide into "
                         "stages and microbatches)")
    ap.add_argument("--virtual-stages", type=int, default=2,
                    help="virtual stages per pipe device (interleaved "
                         "schedule only)")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="microbatches per step for the pipeline schedule")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--deadline-s", type=float, default=5.0)
    ap.add_argument("--profile", type=int, default=0, metavar="N",
                    help="capture a jax.profiler trace of the first N steps "
                         "(trace directory printed at exit)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = _reduce(cfg)

    n_dev = len(jax.devices())
    n_data = args.data or max(1, n_dev // (args.model * max(args.pipe, 1)))
    mesh = make_debug_mesh(n_data, args.model, pipe=args.pipe)
    rules = make_default_rules(batch_axes(mesh))
    print(f"[train] {cfg.name} ({cfg.family}) on mesh {dict(mesh.shape)} "
          f"params~{cfg.param_count()/1e6:.1f}M", flush=True)

    pipe_sched = None
    if args.pipeline_schedule != "none":
        pipe_sched = get_schedule(
            args.pipeline_schedule,
            num_virtual=(args.virtual_stages
                         if args.pipeline_schedule == "interleaved" else None))
        n_stages = pipe_axis_size(mesh) * pipe_sched.num_virtual
        mode = ("stage-sharded execution" if n_stages > 1
                else "cost model only (1 stage)")
        print(f"[train] pipeline {pipe_sched.name} ({mode}): "
              f"{pipe_sched.summary(n_stages, args.microbatches)}", flush=True)

    ocfg = OptimizerConfig(kind=args.optimizer, grad_clip=1.0)
    policy = (QuantPolicy(grad_scale=64.0) if args.quantize
              else QuantPolicy.off())
    policy = dataclasses.replace(policy, kernel_backend=args.kernel_backend,
                                 compress_dw=args.compress_dw,
                                 overlap=args.overlap,
                                 overlap_depth=args.overlap_depth,
                                 dw_transport=args.transport,
                                 stochastic=args.stochastic,
                                 quantize_updates=args.quantize_updates,
                                 bit_anneal=args.bit_anneal)
    bits = default_bits(cfg, enabled=args.quantize)

    if args.bit_search:
        from repro.search import export as bit_export
        from repro.search.sensitivity import SweepConfig, run_sweep_lm
        if not args.quantize:
            print("[train] note: --bit-search without --quantize — the "
                  "sweep runs quantized probes but training stays fp32",
                  flush=True)
        sweep = SweepConfig(num_groups=args.bit_search,
                            target=args.bit_target,
                            probe_steps=args.bit_probe_steps,
                            batch=args.global_batch, lr=args.lr)
        t_sweep = time.time()
        bit_plan = run_sweep_lm(cfg, ocfg, sweep, seq_len=args.seq_len,
                                log=lambda s: print(f"[bit-search] {s}",
                                                    flush=True))
        print(f"[train] bit-search ({bit_plan.probes} probes, "
              f"{time.time() - t_sweep:.1f}s): {bit_plan.describe()}",
              flush=True)
        out_dir = args.ckpt_dir or "artifacts"
        bit_plan.save(f"{out_dir}/bit_plan.json")
        serve_plan = bit_export.to_serve_plan(bit_plan)
        bit_export.save_serve_plan(serve_plan, f"{out_dir}/bit_plan_serve.json")
        parity = bit_export.verify_train_serve_parity(bit_plan)
        print(f"[train] train<->serve int8 parity: "
              f"{'OK' if parity['ok'] else 'VIOLATED'} {parity}", flush=True)
        bits["blocks"] = bit_plan.to_bit_schedule(enabled=args.quantize)
    sched = cosine_schedule(args.lr, warmup=max(10, args.steps // 20),
                            total=args.steps)

    # params, optimizer state and (below) every batch are placed on the
    # mesh by the sharding rules, on fresh runs and restores alike; the
    # state is initialized in one jitted call straight into that placement
    def init_state(key):
        params = lm.init_params(key, cfg)
        return params, init_train_state(params, ocfg)

    key = jax.random.key(args.seed)
    p_shapes, o_shapes = jax.eval_shape(init_state, key)
    p_specs = param_pspecs(cfg, p_shapes, mesh)
    state_sh = (to_named(p_specs, mesh),
                to_named(opt_pspecs(cfg, o_shapes, p_specs, mesh), mesh))
    params, opt_state = jax.jit(init_state, out_shardings=state_sh)(key)
    start_step = 0

    plan = FaultPlan.from_env(args.fault_plan)
    if plan is not None:
        print(f"[train] fault plan: {plan.describe()}", flush=True)

    # restore BEFORE transport priming: the checkpoint's resume payload
    # carries the killed run's measured transport decisions, and installing
    # them first keeps the resumed collective schedule (and its numerics)
    # identical instead of re-measuring on a possibly noisier machine
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), ckpt_step, extra = restore_checkpoint(
            args.ckpt_dir, (params, opt_state), shardings=state_sh)
        start_step = apply_resume_extra(extra, cfg, ckpt_step,
                                        anneal=args.bit_anneal)
        print(f"[train] resumed from step {start_step}", flush=True)

    if args.overlap == "on" and args.transport == "auto" and n_data > 1:
        # measure ring-vs-psum EAGERLY for this model's per-layer dW leaf
        # sizes so the traced step consults real decisions, not the
        # platform model (inside jit no measurement can run); restored
        # checkpoint decisions above are cache hits and are NOT re-measured
        from repro.dist.async_collectives import prime_transport_cache
        leaf_bytes = sorted({
            int(np.asarray(jnp.asarray(x).shape).prod() // cfg.num_layers) * 4
            for x in jax.tree.leaves(params["blocks"])})
        decided = prime_transport_cache(leaf_bytes, n_data,
                                        compressed=args.compress_dw)
        picks = ", ".join(f"{b // 1024}kb->{t}" for b, t in decided.items())
        print(f"[train] transport autotuner (g={n_data}): {picks}",
              flush=True)

    # prime the kernel tune cache for this run's matmul shapes, same
    # rationale as the transport cache: the traced step consults stable
    # decisions, and entries restored from the checkpoint above are cache
    # hits (kept with their restored: provenance, never re-derived)
    from repro.kernels.ops import prime_tune_cache, train_tune_shapes
    tuned = prime_tune_cache(train_tune_shapes(cfg, args.global_batch,
                                               args.seq_len))
    hits = sum(1 for d in tuned.values() if d is not None)
    print(f"[train] kernel tune cache primed: {hits}/{len(tuned)} shape(s) "
          f"fit VMEM", flush=True)

    ckpt = (AsyncCheckpointer(args.ckpt_dir,
                              fault=plan.ckpt_fault if plan else None)
            if args.ckpt_dir else None)

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq_len, args.global_batch,
                            seed=args.seed)
    fetch = plan.wrap_fetch(ds.batch_at) if plan else ds.batch_at
    loader = StragglerTolerantLoader(fetch, deadline_s=args.deadline_s,
                                     start_step=start_step)

    step_fn = jax.jit(
        make_train_step(
            cfg, policy, ocfg,
            StepOptions(
                engine=args.engine,
                pipeline_schedule=pipe_sched,
                pipeline_stages=(pipe_axis_size(mesh) * pipe_sched.num_virtual
                                 if pipe_sched else None),
                num_microbatches=args.microbatches if pipe_sched else None,
                bit_anneal=args.bit_anneal)),
        donate_argnums=(0, 1))

    def ckpt_extra(next_step):
        return capture_resume_extra(cfg, next_step, loader=loader,
                                    user_extra={"loss": losses[-1]},
                                    anneal=args.bit_anneal)

    def maybe_flip(next_step):
        # bit-flip drills corrupt a LANDED checkpoint: join the async write
        # first, then flip (the manifest keeps the original crc, so a later
        # restore must detect the mismatch and fall back)
        if plan is not None and next_step in plan.flip_steps():
            ckpt.wait()
            plan.corrupt_checkpoint(args.ckpt_dir, next_step)

    losses, step_seconds = [], []
    trace_dir, tracing = None, False
    if args.profile > 0:
        trace_dir = tempfile.mkdtemp(prefix="repro-trace-train-")
    t0 = time.time()
    try:
        with jax.set_mesh(mesh), activation_sharding_ctx(rules):
            for step in range(start_step, args.steps):
                if trace_dir and step == start_step:
                    jax.profiler.start_trace(trace_dir)
                    tracing = True
                if plan is not None:
                    plan.check_crash(step)
                t_step = time.time()
                batch = dict(loader.get(step))
                # the synthetic LM loader only makes tokens/labels; encdec
                # and vlm need their modality-side inputs too (deterministic
                # per step, so checkpoint-resume replays the same stream)
                bsz = batch["tokens"].shape[0]
                if cfg.family == "encdec" and "frames" not in batch:
                    batch["frames"] = jax.random.normal(
                        jax.random.fold_in(jax.random.key(2), step),
                        (bsz, cfg.encoder_seq, cfg.d_model), jnp.float32)
                if cfg.family == "vlm" and "patch_embeds" not in batch:
                    batch["patch_embeds"] = jax.random.normal(
                        jax.random.fold_in(jax.random.key(3), step),
                        (bsz, cfg.num_patches, cfg.d_model), jnp.float32)
                batch = jax.device_put(
                    batch, to_named(batch_pspecs(batch, mesh), mesh))
                hyper = Hyper(lr=jnp.float32(sched(step)),
                              step=jnp.int32(step))
                rng = (jax.random.fold_in(jax.random.key(1), step)
                       if args.stochastic else None)
                params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                     hyper, bits, rng)
                losses.append(float(metrics["loss"]))
                step_seconds.append(time.time() - t_step)
                if tracing and step - start_step + 1 >= args.profile:
                    jax.profiler.stop_trace()
                    tracing = False
                if step % args.log_every == 0 or step == args.steps - 1:
                    dt = time.time() - t0
                    print(f"step {step:5d} loss {losses[-1]:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"lr {sched(step):.2e} {dt:.1f}s "
                          f"data_skips={loader.skips}", flush=True)
                if ckpt and step and step % args.ckpt_every == 0:
                    ckpt.save(step + 1, (params, opt_state),
                              extra=ckpt_extra(step + 1))
                    maybe_flip(step + 1)
        if ckpt:
            ckpt.save(args.steps, (params, opt_state),
                      extra=ckpt_extra(args.steps))
            ckpt.wait()
            maybe_flip(args.steps)
    finally:
        # close() flushes the final in-flight write and surfaces any
        # background error even when the loop raises; only an injected
        # crash (os._exit) skips it — by design
        if tracing:
            jax.profiler.stop_trace()
        if ckpt:
            ckpt.close()
        loader.close()
    if trace_dir:
        print(f"[train] profiler trace ({args.profile} step(s)): {trace_dir}",
              flush=True)
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({np.mean(losses[:5]):.3f} -> {np.mean(losses[-5:]):.3f} smoothed)",
          flush=True)
    print(f"[train] kernel paths: {kops.format_kernel_traces()}", flush=True)
    return {"losses": losses, "step_seconds": step_seconds}


if __name__ == "__main__":
    main()
