"""Train/eval step builders: the TaxoNN engine vs the autodiff baseline.

``make_train_step(cfg, policy, optim_cfg, engine)`` returns a jit-able

    step(params, opt_state, batch, hyper, bits) -> (params, opt_state, metrics)

engine="taxonn"   — the paper's unrolled G-chain with per-layer fused update
engine="autodiff" — monolithic jax.grad + global optimizer apply (the
                    "conventional accelerator" baseline the paper compares
                    against; also the correctness oracle for the engine)

``bits`` is a dict of runtime BitSchedules keyed by stack name ("blocks",
and "enc_blocks" for encdec).  One compiled step serves every schedule.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.taxonn import (
    QuantPolicy,
    _bits_xs,
    apply_stacked_updates,
    backward_stack,
    default_bits_for,
    forward_stack,
    grad_tap,
    grad_tap_stochastic,
    quantize_weight_tree,
)
from repro.kernels.ops import kernel_backend_ctx, resolve_backend
from repro.quant.fixed_point import quantize_ste
from repro.util.scan import xscan
from repro.util.scopes import scoped
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import Hyper, OptimizerConfig, apply_update, init_opt_state

Array = jax.Array

AUX_COEF = lm.AUX_COEF


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------

STACK_KEYS = ("blocks", "enc_blocks")
SHARED_KEYS = ("shared_attn",)


def boundary_keys(params: dict):
    return tuple(k for k in params
                 if k not in STACK_KEYS and k not in SHARED_KEYS)


def init_train_state(params: dict, optim_cfg: OptimizerConfig) -> dict:
    """Optimizer state mirrored on the params' top-level grouping so the
    engine can scan per-layer slices of each stack's state."""
    return {k: init_opt_state(v, optim_cfg) for k, v in params.items()}


def default_bits(cfg: ModelConfig, enabled: bool = True) -> dict:
    n = num_scan_units(cfg)
    bits = {"blocks": default_bits_for(n, enabled)}
    if cfg.family == "encdec":
        bits["enc_blocks"] = default_bits_for(cfg.num_encoder_layers, enabled)
    return bits


def num_scan_units(cfg: ModelConfig) -> int:
    """Engine-visible layers in the main stack (hybrid scans groups)."""
    if cfg.family == "hybrid":
        return lm.hybrid_groups(cfg)[0]
    return cfg.num_layers


# ---------------------------------------------------------------------------
# Resume-state capture: everything a bitwise restart needs beyond params
# ---------------------------------------------------------------------------

RESUME_SCHEMA = 1


def capture_resume_extra(cfg: ModelConfig, step: int, *, loader=None,
                         user_extra: Optional[dict] = None,
                         anneal=None) -> dict:
    """The checkpoint ``extra`` payload that makes a restart BITWISE.

    (params, opt_state) alone under-specify a resumed step: the restarted
    loop also needs (a) the data-pipeline step, so the step-indexed loader
    replays the exact batch stream, (b) the stochastic-rounding RNG
    convention — the engine folds a fixed base key with the step index, so
    recording the step pins the whole stream, and (c) the primed transport
    cache, so the resumed backward scan instantiates the SAME collective
    schedule the killed run measured (a re-measurement could flip a
    ring/psum/scatter decision and change reduction order), and (d) the
    kernel tune cache, so a resumed run replays the SAME block-shape /
    fusion decisions instead of re-deriving them.  Everything is
    msgpack-scalar/str, so it rides the checkpoint manifest unchanged.
    """
    from repro.dist.async_collectives import transport_cache_snapshot
    from repro.kernels.ops import tune_cache_snapshot
    extra = {
        "resume_schema": RESUME_SCHEMA,
        "arch": cfg.name,
        "family": cfg.family,
        "data_step": int(step),
        "transport_cache": transport_cache_snapshot(),
        "tune_cache": tune_cache_snapshot(),
    }
    if anneal is not None:
        # record the bit-anneal spec: the annealed bits are a pure function
        # of the step, so resume is bitwise automatically — the spec rides
        # along only to GUARD against resuming under a different ramp
        from repro.search.anneal import AnnealSchedule
        extra["bit_anneal"] = AnnealSchedule.parse(anneal).spec
    if loader is not None:
        extra["loader"] = {"served": int(loader.served),
                           "skips": int(loader.skips),
                           "stale_drops": int(getattr(loader, "stale_drops",
                                                      0))}
    if user_extra:
        extra.update(user_extra)
    return extra


def apply_resume_extra(extra: dict, cfg: ModelConfig,
                       ckpt_step: int, *, anneal=None) -> int:
    """Validate + install a checkpoint's resume payload.

    Rejects a checkpoint written by a different arch (restoring qwen state
    into gemma is silent corruption the shape check alone may not catch),
    installs the persisted transport-cache decisions, and returns the data
    step to resume from (falling back to the checkpoint step for pre-schema
    checkpoints, whose save convention was step == next data step).
    """
    extra = extra or {}
    arch = extra.get("arch")
    if arch is not None and arch != cfg.name:
        raise ValueError(
            f"checkpoint was written by arch {arch!r}; refusing to resume "
            f"it as {cfg.name!r}")
    ckpt_anneal = extra.get("bit_anneal")
    cur_anneal = None
    if anneal is not None:
        from repro.search.anneal import AnnealSchedule
        cur_anneal = AnnealSchedule.parse(anneal).spec
    if ckpt_anneal is not None and cur_anneal is not None \
            and ckpt_anneal != cur_anneal:
        raise ValueError(
            f"checkpoint was annealed under {ckpt_anneal!r}; resuming with "
            f"{cur_anneal!r} would change the bit ramp mid-run (pass the "
            f"same --bit-anneal spec to resume)")
    if (ckpt_anneal is None) != (cur_anneal is None):
        warnings.warn(
            f"bit-anneal mismatch at resume: checkpoint={ckpt_anneal!r} "
            f"current={cur_anneal!r} — the effective bit schedule changes "
            f"at the restart boundary", RuntimeWarning, stacklevel=2)
    cache = extra.get("transport_cache")
    if cache:
        from repro.dist.async_collectives import load_transport_cache
        n = load_transport_cache(cache)
        if n:
            print(f"[train] restored {n} transport-cache decision(s) from "
                  f"checkpoint", flush=True)
    tune = extra.get("tune_cache")
    if tune:
        from repro.kernels.ops import load_tune_cache
        n = load_tune_cache(tune)
        if n:
            print(f"[train] restored {n} tune-cache decision(s) from "
                  f"checkpoint", flush=True)
    return int(extra.get("data_step", ckpt_step))


# ---------------------------------------------------------------------------
# Per-family stack bodies: body(params_slice, shared, x, bits_l) -> (y, aux)
# ---------------------------------------------------------------------------

def _make_body(cfg: ModelConfig, positions, enc_out_in_shared: bool = False,
               moe_aux_parts: bool = False):
    return scoped("block", _family_body(cfg, positions, moe_aux_parts))


def _family_body(cfg: ModelConfig, positions, moe_aux_parts: bool):
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        def body(p, shared, x, b_l):
            return B.transformer_block(p, x, cfg, positions,
                                       moe_aux_parts=moe_aux_parts)
        return body

    if fam == "ssm":
        def body(p, shared, x, b_l):
            return B.mamba_block(p, x, cfg, positions)
        return body

    if fam == "hybrid":
        def body(gp, shared, x, b_l):
            h, _ = B.transformer_block(shared, x, cfg, positions)

            @jax.checkpoint
            def inner(hh, p):
                h2, aux = B.mamba_block(p, hh, cfg, positions)
                return h2, aux
            h, auxs = xscan(inner, h, gp)
            return h, jnp.sum(auxs)
        return body

    if fam == "encdec":
        def body(p, shared, x, b_l):
            (enc_out,) = shared
            return B.decoder_block(p, x, cfg, positions, enc_out)
        return body

    raise ValueError(fam)


def _enc_body(cfg: ModelConfig, positions):
    def body(p, shared, x, b_l):
        return B.transformer_block(p, x, cfg, positions, causal=False)
    return scoped("block", body)


# ---------------------------------------------------------------------------
# Boundary (embed / head) functions
# ---------------------------------------------------------------------------

def _embed_fn(cfg: ModelConfig, batch, policy: QuantPolicy, bits0):
    """x0 from the boundary params; quantized with the first layer's format."""
    def f(bnd):
        emb = bnd["embed"]
        if policy.quantize_weights:
            emb = quantize_weight_tree(emb, bits0["w_i"], bits0["w_f"],
                                       bits0["enabled"], True)
        p = {"embed": emb}
        if cfg.family == "vlm":
            p["mm_proj"] = bnd["mm_proj"]
        x0, _ = lm.embed_input(p, cfg, batch)
        return x0
    return scoped("embed", f)


def _head_fn(cfg: ModelConfig, batch, policy: QuantPolicy, bits_last,
             grad_scale: float):
    np_off = batch["patch_embeds"].shape[1] if cfg.family == "vlm" else 0

    def f(bnd, xf):
        x = L.apply_norm(bnd["final_norm"], xf, cfg)
        if np_off:
            x = x[:, np_off:, :]
        w = bnd["embed"].T if cfg.tie_embeddings else bnd["lm_head"]
        if policy.quantize_weights:
            w = quantize_weight_tree(w, bits_last["w_i"], bits_last["w_f"],
                                     bits_last["enabled"], True)
        loss, metrics = lm.ce_from_weight(w, cfg, x, batch["labels"])
        return loss, metrics
    return scoped("head_loss", f)


def _bits_edge(bits, idx):
    return {"w_i": bits.w_i[idx], "w_f": bits.w_f[idx],
            "a_i": bits.a_i[idx], "a_f": bits.a_f[idx],
            "g_i": bits.g_i[idx], "g_f": bits.g_f[idx],
            "enabled": bits.enabled}


# ---------------------------------------------------------------------------
# Stage-sharded stack execution through dist.pipeline
# ---------------------------------------------------------------------------

def pipeline_exec_capabilities(cfg: ModelConfig,
                               policy: QuantPolicy) -> dict:
    """What the stage-sharded pipeline path can execute, per feature.

    Every entry maps a requirement of this (cfg, policy) combination to
    whether the pipeline path supports it.  Since the shared-operand story
    (broadcast-class operands replicated/sliced per stage, reduce-class aux
    summed post-drain) and the quant-feature parity work landed, every
    family and every QuantPolicy feature is supported — the map exists so
    ``_check_pipeline_exec`` DETECTS a missing capability instead of
    hard-coding a family allowlist, and so callers (tests, the train
    driver) can introspect support instead of parsing error text.
    """
    known = cfg.family in lm.SHARED_OPERAND_KIND
    return {
        f"family:{cfg.family}": known,
        "stochastic": True,        # per-(layer, batch-row) PRNG threading
        "quantize_updates": True,  # inside the vmapped/overlapped update
        "compress_dw": True,       # per-layer codec in the update tail
        "overlap": True,           # depth-pipelined reduce over dw axes
    }


def _check_pipeline_exec(cfg: ModelConfig, policy: QuantPolicy,
                         num_stages: int) -> None:
    """Build-time validation for executing the stack through dist.pipeline."""
    caps = pipeline_exec_capabilities(cfg, policy)
    active = [f"family:{cfg.family}"]
    active += [f for f in ("stochastic", "quantize_updates", "compress_dw")
               if getattr(policy, f)]
    if policy.overlap == "on":
        active.append("overlap")
    missing = [f for f in active if not caps.get(f, False)]
    if missing:
        raise NotImplementedError(
            f"pipeline execution (pipeline_stages={num_stages} > 1) does "
            f"not support {missing} for this configuration")
    n = num_scan_units(cfg)
    if n % num_stages:
        raise ValueError(
            f"num_layers={n} does not divide into pipeline_stages="
            f"{num_stages} equal stages")


def _unpipe(a, mesh):
    """Constrain an array leaving pipeline_apply to be replicated over the
    mesh (no-op without a pipe-axis mesh or outside a partitionable ctx)."""
    if mesh is None or "pipe" not in getattr(mesh, "axis_names", ()):
        return a
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*([None] * a.ndim))))
    except Exception:
        return a


def _pipeline_stack_forward(body, stacked, bits, policy: QuantPolicy,
                            x0: Array, sched, num_stages: int,
                            num_microbatches: int, mesh, shared=(),
                            shared_kind: str = "none",
                            moe_experts: Optional[int] = None,
                            rng: Optional[Array] = None):
    """Run the blocks stack stage-sharded through dist.pipeline.

    The stack's [L, ...] params reshape to [S, L/S, ...] stages and the
    batch splits into M microbatches; ``pipeline_apply`` executes them
    under ``sched`` with stages placed on the mesh's "pipe" axis.  Each
    stage runs its own layers (unrolled — see the in-body comment on why
    not an inner scan) with the engine's forward quantization, and
    a ``grad_tap`` at every layer input quantizes the backward cotangent —
    so ``jax.vjp`` of this function IS the engine's G-chain (values match
    the sequential scan bit-exactly; per-layer dW matches the reverse
    scan's).  Unlike the scan path the full stacked dW tree materialises
    here: stage-sharding trades the paper's one-layer gradient residency
    for the pipe axis's parallelism.

    Shared operands (``shared_kind``, see ``models.lm.SHARED_OPERAND_KIND``):

    * ``"weights"`` (hybrid's weight-tied attn block): ``shared`` is
      replicated to every stage — each layer quantizes it with its own
      (I,F) just like the scan engine — and the vjp of the broadcast sums
      the per-stage gradients.
    * ``"activation"`` (encdec's encoder output): ``shared`` leaves are
      full-batch activations; each stage slices the rows of the microbatch
      it is currently processing (the microbatch index rides the rotating
      pipeline value), and the slice's vjp scatter-adds the per-stage
      cotangents back into the full-batch gradient.

    Reduce-class side outputs (moe's load-balance aux) ride the pipeline
    value as per-microbatch accumulators and are combined after the drain.
    Because the aux is bilinear in two batch-mean statistics (expert pick
    fraction x mean router prob), each stage writes its layers' per-
    microbatch STATISTICS (``moe_experts`` set) and the post-drain
    recombination averages them over microbatches before the product —
    reproducing the scan engine's full-batch aux (and its gradient)
    instead of the mean of per-microbatch aux values, which differs.
    Families with scalar aux accumulate the scalar and normalize by M.

    With ``policy.stochastic`` and an ``rng`` key, the backward cotangent
    taps round stochastically with noise keyed per (layer, global batch
    row): layer keys fold the unit index, row keys fold ``m * mb + b`` —
    deterministic in (stage, microbatch, layer) and identical to the scan
    engine's full-batch draws.

    Returns ``(y [B, ...], aux_sum scalar)``.
    """
    from repro.dist.pipeline import pipeline_apply
    n_units = jax.tree.leaves(stacked)[0].shape[0]
    bsz = x0.shape[0]
    S, M = num_stages, num_microbatches
    # batch % M validated by the caller (the train step's pipe branch,
    # which needs the quotient before this function can even be built)
    lps = n_units // S
    mbsz = bsz // M
    enabled = bits.enabled
    use_stoch = (policy.quantize_grads and policy.stochastic
                 and rng is not None)
    stage_p = jax.tree.map(lambda a: a.reshape((S, lps) + a.shape[1:]),
                           stacked)
    stage_b = jax.tree.map(lambda a: a.reshape((S, lps) + a.shape[1:]),
                           _bits_xs(bits))
    stage_l = jnp.arange(n_units, dtype=jnp.int32).reshape(S, lps)  # unit
    x_mb = x0.reshape((M, mbsz) + x0.shape[1:])

    def stage_body(bundle, val):
        p_s, b_s, l_s = bundle
        m = val["m"]
        if shared_kind == "activation":
            sh = tuple(jax.lax.dynamic_slice_in_dim(s, m * mbsz, mbsz, 0)
                       for s in shared)
        else:
            sh = shared

        # remat-per-layer (the paper's recompute-in-backward discipline,
        # same as the scan engine's cached-X_i + re-linearize): under
        # jax.vjp the PRIMAL pass runs this body un-linearized, which is
        # what keeps the pipeline's forward values — and therefore the
        # loss — bit-identical to the scan engine's plain forward, and the
        # backward re-linearizes each layer at exactly the per-layer
        # inputs the forward produced (the engine's cached X_i).  Without
        # it, partial-eval restructures the body (residual materialisation
        # changes FMA/fusion rounding) and sub-ulp drift leaks into the
        # forward.
        @functools.partial(jax.checkpoint, prevent_cse=False)
        def layer(carry, xs_l):
            p_l, b_l, l_idx = xs_l
            hh = carry["h"]
            if policy.quantize_grads:
                if use_stoch:
                    kd = jax.random.key_data(jax.random.fold_in(rng, l_idx))
                    hh = grad_tap_stochastic(hh, b_l["g_i"], b_l["g_f"],
                                             enabled, kd, m * mbsz)
                else:
                    hh = grad_tap(hh, b_l["g_i"], b_l["g_f"], enabled)
            if policy.quantize_acts:
                hq = (enabled * quantize_ste(hh.astype(jnp.float32),
                                             b_l["a_i"], b_l["a_f"])
                      + (1.0 - enabled) * hh.astype(jnp.float32)
                      ).astype(hh.dtype)
            else:
                hq = hh
            wq = quantize_weight_tree(p_l, b_l["w_i"], b_l["w_f"], enabled,
                                      policy.quantize_weights)
            sq = (quantize_weight_tree(sh, b_l["w_i"], b_l["w_f"], enabled,
                                       policy.quantize_weights)
                  if shared_kind == "weights" else sh)
            y, aux_l = body(wq, sq, hq, b_l)
            new = dict(carry, h=y)
            if moe_experts:
                # this unit's statistics land in its own row; other units'
                # rows (written by other stages) pass through untouched
                new["frac"] = jax.lax.dynamic_update_index_in_dim(
                    carry["frac"], aux_l["frac"], l_idx, 0)
                new["p"] = jax.lax.dynamic_update_index_in_dim(
                    carry["p"], aux_l["p"], l_idx, 0)
            else:
                new["aux"] = carry["aux"] + aux_l
            return new, None

        # the per-stage layer loop is UNROLLED, not scanned: partial-eval
        # of an inner lax.scan stacks per-layer residuals, which perturbs
        # fusion inside the scan body (observed as sub-ulp forward drift
        # on the mamba families, amplified to grid steps by the act
        # quantizer); the unrolled graph keeps each remat'd layer's
        # primal bit-identical to the plain forward, at the cost of
        # per-tick HLO growing with L/S.  Pipeline stages keep L/S small
        # by construction, and the outer tick scan stays rolled.
        carry = {k: v for k, v in val.items() if k != "m"}
        for j in range(lps):
            xs_j = (jax.tree.map(lambda a: a[j], p_s),
                    {k: v[j] for k, v in b_s.items()}, l_s[j])
            carry, _ = layer(carry, xs_j)
        return dict(carry, m=m)

    val0 = {"h": x_mb, "m": jnp.arange(M, dtype=jnp.int32)}
    if moe_experts:
        val0["frac"] = jnp.zeros((M, n_units, moe_experts), jnp.float32)
        val0["p"] = jnp.zeros((M, n_units, moe_experts), jnp.float32)
    else:
        val0["aux"] = jnp.zeros((M,), jnp.float32)
    out = pipeline_apply((stage_p, stage_b, stage_l), val0, stage_body,
                         mesh, schedule=sched)
    # the collected outputs leave the pipe axis here: pin them replicated
    # so the head (and the aux recombination) runs the same single-program
    # reductions as the scan reference instead of partitioner-split ones
    # (sharded reductions reassociate, and the quantizers amplify that)
    out = jax.tree.map(lambda a: _unpipe(a, mesh), out)
    y = out["h"].reshape((bsz,) + out["h"].shape[2:])
    if moe_experts:
        # full-batch statistics = mean of per-microbatch statistics; the
        # bilinear recombination AFTER the mean reproduces the scan
        # engine's full-batch aux and, through this vjp, its gradient
        frac = jnp.mean(out["frac"], axis=0)          # [L, E]
        probs_mean = jnp.mean(out["p"], axis=0)       # [L, E]
        aux_sum = jnp.sum(jax.vmap(L.moe_aux_from_stats)(frac, probs_mean))
    else:
        aux_sum = jnp.sum(out["aux"]) / M
    return y, aux_sum


# ---------------------------------------------------------------------------
# The TaxoNN train step
# ---------------------------------------------------------------------------

def _pipeline_metrics(pipeline_schedule, pipeline_stages, num_microbatches):
    """Resolve the pipeline knob into (Schedule | None, static metric dict).

    The schedule is validated eagerly (unknown names and uneven
    virtual-stage counts fail at step-build time, not mid-training) and its
    tick-table estimates are folded into every step's metrics so the
    bubble/memory tradeoff is visible in training logs.
    """
    if pipeline_schedule is None:
        return None, {}
    from repro.dist.pipeline import get_schedule
    sched = get_schedule(pipeline_schedule)
    S = int(pipeline_stages) if pipeline_stages else 1
    M = int(num_microbatches) if num_microbatches else 1
    sched.validate(S, M)
    plan = sched.plan(S, M)
    return sched, {
        "pipe_bubble": jnp.float32(plan.bubble),
        "pipe_ticks": jnp.int32(plan.num_ticks),
        "pipe_peak_mb": jnp.int32(plan.peak_activation_microbatches),
    }


@dataclasses.dataclass(frozen=True)
class StepOptions:
    """Everything that selects HOW a train step executes, in one frozen
    value — the successor of ``make_train_step``'s kwarg sprawl (engine,
    kernel_backend, pipeline_*, overlap, transport each arrived as a new
    keyword in a different PR).  ``None`` fields defer to the policy
    (kernel_backend/overlap/transport) or mean "feature off" (pipeline_*).

    Build one directly, or seed it from a policy's knobs and override:

        opts = StepOptions(engine="autodiff")
        opts = StepOptions.from_policy(policy, overlap="on")
        step = make_train_step(cfg, policy, ocfg, opts)
    """

    engine: str = "taxonn"
    kernel_backend: Optional[str] = None
    pipeline_schedule: Any = None
    pipeline_stages: Optional[int] = None
    num_microbatches: Optional[int] = None
    overlap: Optional[str] = None
    transport: Optional[str] = None
    bit_anneal: Any = None  # spec str | AnnealSchedule | None

    def __post_init__(self):
        if self.engine not in ("taxonn", "autodiff"):
            raise ValueError(f"engine must be 'taxonn' or 'autodiff', "
                             f"got {self.engine!r}")
        if isinstance(self.bit_anneal, str):
            from repro.search.anneal import AnnealSchedule
            object.__setattr__(self, "bit_anneal",
                               AnnealSchedule.parse(self.bit_anneal))
        elif self.bit_anneal is not None:
            from repro.search.anneal import AnnealSchedule
            if not isinstance(self.bit_anneal, AnnealSchedule):
                raise ValueError(
                    f"bit_anneal must be an anneal spec string or an "
                    f"AnnealSchedule, got {type(self.bit_anneal).__name__}")
        if self.kernel_backend not in (None, "off", "emulate", "int8", "auto"):
            raise ValueError(f"kernel_backend must be 'off', 'emulate', "
                             f"'int8' or 'auto', got {self.kernel_backend!r}")
        if self.overlap not in (None, "off", "on"):
            raise ValueError(f"overlap must be 'off' or 'on', "
                             f"got {self.overlap!r}")
        if self.transport not in (None, "auto", "ring", "psum", "scatter"):
            raise ValueError(f"transport must be 'auto', 'ring', 'psum' or "
                             f"'scatter', got {self.transport!r}")

    @classmethod
    def from_policy(cls, policy: QuantPolicy, **overrides) -> "StepOptions":
        """Seed the execution knobs from the policy's own fields (the
        values ``make_train_step`` would resolve to anyway), then apply
        explicit overrides — handy when one policy drives several step
        variants."""
        base = dict(kernel_backend=policy.kernel_backend,
                    overlap=policy.overlap,
                    transport=policy.dw_transport,
                    bit_anneal=getattr(policy, "bit_anneal", None))
        base.update(overrides)
        return cls(**base)

    def replace(self, **kw) -> "StepOptions":
        return dataclasses.replace(self, **kw)


_DEPRECATED_STEP_KWARGS = ("engine", "kernel_backend", "pipeline_schedule",
                           "pipeline_stages", "num_microbatches", "overlap",
                           "transport")


def make_train_step(cfg: ModelConfig, policy: Optional[QuantPolicy] = None,
                    optim_cfg: Optional[OptimizerConfig] = None,
                    options: Optional[StepOptions] = None,
                    **deprecated_kwargs):
    """Build the train step described by ``options`` (a ``StepOptions``).

    The legacy per-knob keywords (``engine=``, ``kernel_backend=``,
    ``pipeline_schedule=``, ``pipeline_stages=``, ``num_microbatches=``,
    ``overlap=``, ``transport=``) still work through a shim that folds
    them into a ``StepOptions`` and emits a ``DeprecationWarning`` — new
    code should pass ``options=StepOptions(...)`` instead.
    """
    if deprecated_kwargs:
        unknown = set(deprecated_kwargs) - set(_DEPRECATED_STEP_KWARGS)
        if unknown:
            raise TypeError(f"make_train_step got unexpected keyword "
                            f"arguments {sorted(unknown)}")
        warnings.warn(
            f"make_train_step kwargs {sorted(deprecated_kwargs)} are "
            f"deprecated; pass options=StepOptions(...) instead",
            DeprecationWarning, stacklevel=2)
        if options is not None:
            clash = [k for k, v in deprecated_kwargs.items()
                     if getattr(options, k) is not None and v is not None
                     and (k != "engine" or v != options.engine)]
            if clash:
                raise ValueError(f"both options= and legacy kwargs set "
                                 f"{sorted(clash)}")
        options = dataclasses.replace(options or StepOptions(),
                                      **deprecated_kwargs)
    options = options or StepOptions()
    return _make_train_step(cfg, policy, optim_cfg, options)


def _make_train_step(cfg: ModelConfig, policy: Optional[QuantPolicy],
                     optim_cfg: Optional[OptimizerConfig],
                     options: StepOptions):
    """``kernel_backend`` overrides ``policy.kernel_backend`` ("off" |
    "emulate" | "int8" | "auto"; auto = off on CPU, int8 on TPU) and selects
    the datapath for the dense-unit matmuls in the step's hot loops.

    ``overlap`` ("off" | "on") overrides ``policy.overlap``: with "on" the
    engine's backward scan software-pipelines each layer's dW all-reduce
    ``policy.overlap_depth`` scan steps deep (start at layer i, wait while
    the next ``depth`` layers compute — see ``core.taxonn.backward_stack``
    / ``dist.async_collectives``).

    ``transport`` ("auto" | "ring" | "psum" | "scatter") overrides
    ``policy.dw_transport``: which wire the overlapped dW reduce rides —
    "auto" asks the per-bucket transport autotuner
    (``dist.async_collectives.decide_transport``; ``REPRO_TRANSPORT``
    forces it globally), "ring" the chunked ppermute ring, "psum" the
    fused blocking collective, "scatter" the reduce-scatter +
    sharded-update + all-gather path (dense SGD only; degrades to psum
    otherwise).  Prime the autotuner's measured decisions BEFORE tracing
    via ``dist.async_collectives.prime_transport_cache``; inside the
    trace it falls back to cached decisions or a platform model.

    ``pipeline_schedule`` ("gpipe" | "1f1b" | "interleaved" or a
    ``repro.dist.pipeline.Schedule``) declares the pipeline schedule this
    step runs under when the mesh has a "pipe" axis of ``pipeline_stages``
    devices and the batch is split into ``num_microbatches`` microbatches.
    It is validated at build time and surfaces the schedule's tick-table
    estimates (``pipe_bubble`` / ``pipe_ticks`` / ``pipe_peak_mb``) in the
    step metrics.  With ``pipeline_stages > 1`` the TaxoNN engine's blocks
    stack EXECUTES stage-sharded through ``dist.pipeline.pipeline_apply``
    (the schedule places stages on the mesh's "pipe" axis; see
    ``_pipelined_stack``); the returned step exposes the schedule as
    ``step.pipeline_schedule``.
    """
    policy = policy or QuantPolicy.off()
    if options.overlap is not None:
        policy = dataclasses.replace(policy, overlap=options.overlap)
    if options.transport is not None:
        policy = dataclasses.replace(policy, dw_transport=options.transport)
    optim_cfg = optim_cfg or OptimizerConfig()
    backend = resolve_backend(
        options.kernel_backend if options.kernel_backend is not None
        else getattr(policy, "kernel_backend", "auto"))
    engine = options.engine
    pipeline_stages = options.pipeline_stages
    sched, pipe_metrics = _pipeline_metrics(
        options.pipeline_schedule, options.pipeline_stages,
        options.num_microbatches)
    anneal = options.bit_anneal
    if anneal is None:
        pol_spec = getattr(policy, "bit_anneal", None)
        if pol_spec:
            from repro.search.anneal import AnnealSchedule
            anneal = AnnealSchedule.parse(pol_spec)

    if engine == "autodiff":
        def auto_step(params, opt_state, batch, hyper: Hyper, bits=None,
                      rng=None):  # rng accepted for signature parity
            with kernel_backend_ctx(backend):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: lm.loss_fn(p, cfg, batch), has_aux=True)(params)
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
            new_params, new_opt = {}, {}
            for k in params:  # grouped like the engine's state layout
                new_params[k], new_opt[k] = apply_update(
                    params[k], grads[k], opt_state[k], hyper, optim_cfg)
            metrics["grad_norm"] = jnp.sqrt(gsq)
            metrics.update(pipe_metrics)
            return new_params, new_opt, metrics
        auto_step.pipeline_schedule = sched
        auto_step.bit_anneal = anneal  # accepted for parity; bits unused
        return auto_step

    if engine != "taxonn":
        raise ValueError(engine)

    fam = cfg.family
    scale = policy.grad_scale
    pipe_exec = sched is not None and pipeline_stages and int(
        pipeline_stages) > 1
    if pipe_exec:
        _check_pipeline_exec(cfg, policy, int(pipeline_stages))

    def _step_impl(params, opt_state, batch, hyper: Hyper, bits: dict,
                   rng: Optional[Array] = None):
        if rng is not None:
            # normalize to a typed key so the scan engine and the pipeline
            # path fold the SAME key stream (legacy uint32 keys wrap here)
            rng = jnp.asarray(rng)
            if not jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
                rng = jax.random.wrap_key_data(rng)
        if anneal is not None:
            # step-indexed F-bit ramp: bits stay traced data, so the anneal
            # composes with the scan, pipeline, overlap and stochastic paths
            # for free, and resume at step N continues the ramp bitwise
            bits = anneal.apply_tree(bits, hyper.step)
        main_bits = bits["blocks"]
        bnd_keys = boundary_keys(params)
        bnd = {k: params[k] for k in bnd_keys}

        tokens = batch["tokens"]
        bsz, tlen = tokens.shape
        total_t = tlen + (batch["patch_embeds"].shape[1]
                          if fam == "vlm" else 0)
        positions = jnp.broadcast_to(jnp.arange(total_t), (bsz, total_t))

        # ---- encoder forward (encdec only) ------------------------------
        enc_caches = enc_out = enc_pos = None
        enc_vjp = None
        if fam == "encdec":
            dt = lm.compute_dtype(cfg)
            frames = batch["frames"].astype(dt)
            enc_x0 = frames + lm._sinusoid(frames.shape[1], cfg.d_model).astype(dt)
            enc_pos = jnp.broadcast_to(
                jnp.arange(frames.shape[1]), (bsz, frames.shape[1]))
            e_last, enc_caches, _ = forward_stack(
                _enc_body(cfg, enc_pos), params["enc_blocks"], (),
                enc_x0, bits["enc_blocks"], policy)
            enc_out, enc_vjp = jax.vjp(
                lambda en, xx: L.apply_norm(en, xx, cfg),
                bnd["enc_norm"], e_last)

        # ---- embed (with VJP for the input-side embedding gradient) -----
        embed_f = _embed_fn(cfg, batch, policy, _bits_edge(main_bits, 0))
        x0, embed_vjp = jax.vjp(embed_f, bnd)

        # ---- main stack forward, caching quantized X_i -------------------
        # hybrid: shared = the weight-tied attn block (quantized per layer)
        # encdec: shared = encoder output ACTIVATION (quantized once here)
        quantize_shared = fam == "hybrid"
        shared = (params["shared_attn"],) if fam == "hybrid" else ()
        if fam == "encdec":
            if policy.quantize_acts:
                eb = _bits_edge(bits["enc_blocks"], -1)
                enc_q = (eb["enabled"] * quantize_ste(
                    enc_out.astype(jnp.float32), eb["a_i"], eb["a_f"])
                    + (1.0 - eb["enabled"]) * enc_out.astype(jnp.float32)
                ).astype(enc_out.dtype)
            else:
                enc_q = enc_out
            shared = (enc_q,)
        body = _make_body(cfg, positions)

        def body_sh(p, sh, x, b_l):
            if fam == "hybrid":
                return body(p, sh[0], x, b_l)
            return body(p, sh, x, b_l)

        pipe_vjp = None
        if pipe_exec:
            # stage-sharded execution through dist.pipeline: the bodies run
            # per-microbatch, so they need microbatch-shaped positions
            S_pipe = int(pipeline_stages)
            M_pipe = int(options.num_microbatches or 1)
            if bsz % M_pipe:
                raise ValueError(f"global batch {bsz} does not divide into "
                                 f"num_microbatches={M_pipe}")
            pos_mb = jnp.broadcast_to(jnp.arange(total_t),
                                      (bsz // M_pipe, total_t))
            body_mb = _make_body(cfg, pos_mb, moe_aux_parts=fam == "moe")

            def body_sh_mb(p, sh, x, b_l):
                if fam == "hybrid":
                    return body_mb(p, sh[0], x, b_l)
                return body_mb(p, sh, x, b_l)

            mesh = jax.sharding.get_abstract_mesh()
            shared_kind = lm.SHARED_OPERAND_KIND[fam]

            def fwd_pipe(blocks, shared_, x0_):
                return _pipeline_stack_forward(
                    body_sh_mb, blocks, main_bits, policy, x0_, sched,
                    S_pipe, M_pipe, mesh, shared=shared_,
                    shared_kind=shared_kind,
                    moe_experts=(cfg.num_experts if fam == "moe" else None),
                    rng=rng)

            # shared rides as a vjp argument: broadcast-class operands
            # (hybrid's weight-tied attn, encdec's encoder output) get
            # their gradient summed across stages by the transpose;
            # reduce-class side outputs (moe's aux statistics) ride the
            # pipeline value and are recombined post-drain into aux_sum
            (x_final, aux_sum), pipe_vjp = jax.vjp(
                fwd_pipe, params["blocks"], shared, x0)
        else:
            x_final, caches, aux_sum = forward_stack(
                body_sh, params["blocks"], shared, x0, main_bits, policy,
                quantize_shared=quantize_shared)

        # ---- head (loss) --------------------------------------------------
        head_f = _head_fn(cfg, batch, policy, _bits_edge(main_bits, -1), scale)
        loss, head_vjp, metrics = jax.vjp(head_f, bnd, x_final, has_aux=True)
        d_bnd_head, G_final = head_vjp(jnp.asarray(scale, jnp.float32))
        metrics["aux"] = aux_sum
        with jax.named_scope("head_loss"):
            metrics["loss_total"] = loss + AUX_COEF * aux_sum

        # ---- the G-chain: reverse scan with fused per-layer updates ------
        if pipe_exec:
            # vjp through the stage-sharded pipeline (grad taps reproduce
            # the engine's per-layer G quantization); the update tail
            # (core.taxonn.apply_stacked_updates) reduces each layer's dW
            # over dw_psum_axes — compressed or dense, overlapped or
            # blocking — quantizes the update (strict-paper mode) and
            # applies it, with the scan engine's per-layer PRNG keys.
            # The aux seed is the scalar loss coefficient; the post-drain
            # recombination inside fwd_pipe distributes it per layer and
            # microbatch by the chain rule.
            d_blocks, dshared, G_in = pipe_vjp(
                (G_final, jnp.asarray(AUX_COEF * scale, jnp.float32)))
            d_blocks = jax.tree.map(
                lambda g: g.astype(jnp.float32) / scale, d_blocks)
            new_blocks, new_blocks_opt, gsq = apply_stacked_updates(
                params["blocks"], d_blocks, opt_state["blocks"], main_bits,
                hyper, policy, optim_cfg, base_key=rng)
        else:
            G_in, new_blocks, new_blocks_opt, dshared, gsq = backward_stack(
                body_sh, params["blocks"], shared, opt_state["blocks"],
                caches, main_bits, G_final, hyper, policy, optim_cfg,
                AUX_COEF, base_key=rng, quantize_shared=quantize_shared)

        new_params = dict(params)
        new_opt = dict(opt_state)
        new_params["blocks"] = new_blocks
        new_opt["blocks"] = new_blocks_opt

        # ---- shared-attn update (hybrid) ---------------------------------
        if fam == "hybrid":
            with jax.named_scope("update"):
                d_shared_params = jax.tree.map(lambda g: g / scale,
                                               dshared[0])
                new_params["shared_attn"], new_opt["shared_attn"] = \
                    apply_update(params["shared_attn"], d_shared_params,
                                 opt_state["shared_attn"], hyper, optim_cfg)
                gsq = gsq + sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(d_shared_params))

        # ---- encoder backward (encdec) ------------------------------------
        d_bnd_enc = None
        if fam == "encdec":
            (d_enc_out,) = dshared  # accumulated over decoder layers (SCALED)
            d_enc_norm, d_e_last = enc_vjp(d_enc_out.astype(enc_out.dtype))
            _, new_enc, new_enc_opt, _, gsq_e = backward_stack(
                _enc_body(cfg, enc_pos), params["enc_blocks"], (),
                opt_state["enc_blocks"], enc_caches, bits["enc_blocks"],
                d_e_last, hyper, policy, optim_cfg, AUX_COEF, base_key=rng)
            new_params["enc_blocks"] = new_enc
            new_opt["enc_blocks"] = new_enc_opt
            gsq = gsq + gsq_e
            d_bnd_enc = jax.tree.map(
                lambda w: jnp.zeros(w.shape, jnp.float32), bnd)
            d_bnd_enc["enc_norm"] = jax.tree.map(
                lambda g: g.astype(jnp.float32) / scale, d_enc_norm)

        # ---- boundary updates (embed gets head + input contributions) ----
        (d_bnd_embed,) = embed_vjp(G_in)
        with jax.named_scope("update"):
            d_bnd = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              + b.astype(jnp.float32)) / scale,
                d_bnd_head, d_bnd_embed)
            if d_bnd_enc is not None:
                d_bnd = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     d_bnd, d_bnd_enc)
            bnd_new, bnd_opt_new = {}, {}
            for k in bnd_keys:
                bnd_new[k], bnd_opt_new[k] = apply_update(
                    bnd[k], d_bnd[k], opt_state[k], hyper, optim_cfg)
                gsq = gsq + sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(d_bnd[k]))
            metrics["grad_norm"] = jnp.sqrt(gsq)
        new_params.update(bnd_new)
        new_opt.update(bnd_opt_new)

        metrics.update(pipe_metrics)
        return new_params, new_opt, metrics

    def step(params, opt_state, batch, hyper: Hyper, bits: dict,
             rng: Optional[Array] = None):
        with kernel_backend_ctx(backend):  # active at trace time
            return _step_impl(params, opt_state, batch, hyper, bits, rng)

    step.pipeline_schedule = sched
    step.bit_anneal = anneal
    return step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = lm.loss_fn(params, cfg, batch)
        return metrics
    return eval_step
