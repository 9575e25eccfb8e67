"""The TaxoNN engine: SGD unrolled into an explicit per-layer G-chain.

This is the paper's Eq. (2)-(9) as a JAX program.  Back-propagation is NOT
delegated to ``jax.grad`` over the whole model; instead it is an explicit
reverse ``lax.scan`` whose carry is the paper's G vector:

    G_i = (G_{i+1} @ W_{i+1}) * f'_i          (Eq. 8)
    dE/dW_i = G_i  (x)  X_i                   (Eq. 9)
    W_i <- W_i - alpha * dE/dW_i              (Eq. 1, fused: step 4)

realised at *layer* granularity: each scan step runs a local VJP of one
layer's body at its cached (quantized) input X_i, quantizes the outgoing G,
and applies the weight update immediately — the full-model gradient tree is
never materialised (gradient lifetime = one scan step, the paper's pipeline
in Fig. 3).  Because the data-parallel all-reduce of each layer's dW is
issued *inside* the scan body, XLA overlaps it with the next layer's
backward compute — the TPU analogue of the paper's timing overlap.

Memory discipline matches the paper: the forward pass caches only each
layer's input X_i (quantized to the activation (I,F) format); everything
else (pre-activations, f') is recomputed in the backward body — this is
remat-per-layer, i.e. the paper's "activation derivation unit" executed on
the fly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.async_collectives import (all_gather_chunks, group_size,
                                          reduce_scatter_chunk,
                                          resolve_leaf_transports,
                                          shard_chunk,
                                          tree_all_reduce_start,
                                          tree_all_reduce_wait)
from repro.dist.collectives import compressed_psum
from repro.optim import OptimizerConfig, Hyper, apply_update
from repro.util.scan import xscan
from repro.util.scopes import scoped
from repro.quant.fixed_point import (
    BitSchedule,
    make_bit_schedule,
    maybe_quantize,
    quantize_ste,
    quantize_stochastic,
    stochastic_round_batched,
)

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Which tensor classes get the per-layer (I,F) treatment (static)."""

    quantize_weights: bool = True
    quantize_acts: bool = True
    quantize_grads: bool = True
    quantize_updates: bool = False   # strict paper mode: q(alpha*dW) in-format
    stochastic: bool = False         # stochastic rounding for grads/updates
    grad_scale: float = 1.0          # loss scaling for the low-bit G chain
    # KernelBackend knob: "off" (pure jnp), "emulate" (Pallas f32 kernels),
    # "int8" (int8 MXU datapath), "auto" (off on CPU, int8 on TPU).
    kernel_backend: str = "auto"
    # Route each layer's dW through the int8 block-scaled wire format inside
    # the backward scan (dist.collectives.compressed_psum).  With
    # ``dw_psum_axes`` naming mesh axes (engine running in a shard_map) the
    # all-reduce moves compressed bytes; with no axes it is the codec
    # round-trip only (single-replica numerics of the same wire format).
    # With axes named and ``compress_dw=False`` the dW all-reduce is a
    # dense psum over those axes.
    compress_dw: bool = False
    dw_psum_axes: tuple = ()
    # Communication-overlapped backward scan ("off" | "on"): layer i STARTS
    # its dW all-reduce (dense or compressed, via
    # dist.async_collectives) and WAITS one scan step later, so the
    # collective overlaps layer i-1's G-step/VJP compute — the paper's TDM
    # overlap applied to the interconnect.  With no ``dw_psum_axes`` this is
    # a pure schedule change (bit-identical results).
    overlap: str = "off"
    # Ring-group size override for the overlapped reduce (None = resolve
    # from the ambient mesh at trace time).
    dw_num_replicas: Optional[int] = None
    # Software-pipeline depth of the overlapped reduce: layer i STARTS its
    # dW all-reduce and the wait lands ``overlap_depth`` scan steps later,
    # keeping that many collectives in flight (clamped to the layer count).
    # Depth 2 gives a ring's hops two layers' compute to hide behind.
    overlap_depth: int = 2
    # Transport for the overlapped dW reduce: "auto" (per-bucket autotuner,
    # dist.async_collectives.decide_transport; REPRO_TRANSPORT overrides),
    # "ring" (chunked ppermute), or "psum" (fused blocking collective at
    # start — one rendezvous per layer — with a free wait).
    dw_transport: str = "auto"
    # Progressive bitwidth-annealing spec ("0:16,200:12,..." — see
    # repro.search.anneal.AnnealSchedule).  Consumed by make_train_step:
    # the effective per-layer F bits become a step-indexed ramp applied on
    # top of the run's BitSchedule.  None = no anneal.
    bit_anneal: Optional[str] = None

    @staticmethod
    def off() -> "QuantPolicy":
        return QuantPolicy(False, False, False, False, False, 1.0)


def default_bits_for(num_units: int, enabled: bool = True) -> BitSchedule:
    """Paper-style default: (2,12) weights/grads, (4,10) acts, ramped tail."""
    return make_bit_schedule(num_units, weight=(2, 12), act=(4, 10),
                             grad=(2, 12), enabled=enabled)


# ---------------------------------------------------------------------------
# Quantization helpers (leaf policies)
# ---------------------------------------------------------------------------

def _is_matmul_leaf(w: Array) -> bool:
    """Quantize matmul weights; keep vector params (norm scales, biases,
    A_log, dt_bias, ...) full precision — the paper's wide accumulator /
    derivation-unit registers."""
    return w.ndim >= 2


def quantize_weight_tree(tree: PyTree, w_i, w_f, enabled: Array,
                         on: bool) -> PyTree:
    if not on:
        return tree
    return jax.tree.map(
        lambda w: maybe_quantize(w, w_i, w_f, enabled) if _is_matmul_leaf(w) else w,
        tree)


def _quant_grad(g: Array, g_i, g_f, enabled: Array, policy: QuantPolicy,
                key: Optional[Array]) -> Array:
    if not policy.quantize_grads:
        return g
    gf = g.astype(jnp.float32)
    if policy.stochastic and key is not None:
        # noise keyed per (layer key, global batch row) — NOT per tensor
        # shape — so the stage-sharded pipeline, which quantizes G one
        # microbatch at a time, makes the exact same draws (see
        # stochastic_round_batched / grad_tap_stochastic)
        q = stochastic_round_batched(gf, g_i, g_f, key, 0)
    else:
        q = quantize_ste(gf, g_i, g_f)
    return (enabled * q + (1.0 - enabled) * gf).astype(g.dtype)


@jax.custom_vjp
def grad_tap(x: Array, g_i, g_f, enabled) -> Array:
    """Identity forward whose COTANGENT is quantized to the (g_i, g_f)
    grid — the G-chain's per-layer ``G <- q(G)`` (Eq. 8's low-bit signal)
    expressed as a forward-graph annotation.  Inserting this at each layer
    input makes a plain ``jax.vjp`` through the stack compute the same
    quantized G-chain the engine's reverse scan does — which is how the
    stage-sharded pipeline path (``dist.pipeline``) keeps engine numerics
    without a hand-written backward."""
    return x


def _grad_tap_fwd(x, g_i, g_f, enabled):
    return x, (g_i, g_f, enabled)


def _grad_tap_bwd(res, ct):
    g_i, g_f, enabled = res
    ctf = ct.astype(jnp.float32)
    q = quantize_ste(ctf, g_i, g_f)
    ct_q = (enabled * q + (1.0 - enabled) * ctf).astype(ct.dtype)
    return (ct_q, jnp.zeros_like(g_i), jnp.zeros_like(g_f),
            jnp.zeros_like(enabled))


grad_tap.defvjp(_grad_tap_fwd, _grad_tap_bwd)


@jax.custom_vjp
def grad_tap_stochastic(x: Array, g_i, g_f, enabled, key_data,
                        offset) -> Array:
    """``grad_tap`` with stochastic rounding: the cotangent is quantized
    with per-batch-row noise drawn from ``fold_in(wrap(key_data),
    offset + b)`` (see ``stochastic_round_batched``).  ``key_data`` is the
    layer key as raw uint32 (``jax.random.key_data``) so the custom_vjp
    signature stays free of typed-key cotangents; ``offset`` is the
    microbatch's first global batch row, which makes the pipeline's
    per-microbatch draws identical to the scan engine's full-batch ones."""
    return x


def _grad_tap_stoch_fwd(x, g_i, g_f, enabled, key_data, offset):
    return x, (g_i, g_f, enabled, key_data, offset)


def _grad_tap_stoch_bwd(res, ct):
    g_i, g_f, enabled, key_data, offset = res
    key = jax.random.wrap_key_data(key_data)
    ctf = ct.astype(jnp.float32)
    q = stochastic_round_batched(ctf, g_i, g_f, key, offset)
    ct_q = (enabled * q + (1.0 - enabled) * ctf).astype(ct.dtype)
    return (ct_q, jnp.zeros_like(g_i), jnp.zeros_like(g_f),
            jnp.zeros_like(enabled), jnp.zeros_like(key_data),
            jnp.zeros_like(offset))


grad_tap_stochastic.defvjp(_grad_tap_stoch_fwd, _grad_tap_stoch_bwd)


def quantize_update(g: Array, b_l: dict, key: Optional[Array],
                    enabled: Array, policy: QuantPolicy,
                    hyper: Hyper) -> Array:
    """Strict-paper mode: quantize the update itself (post-reduction).

    ``q(alpha * dW)`` in the layer's gradient (I,F) format, returned in the
    dW domain (divided back by lr) so the optimizer applies it unchanged.
    Shared by the scan engine's per-layer fused update and the stage-sharded
    pipeline's vmapped/overlapped update paths — both quantize the SAME
    post-reduction tensor with the SAME per-layer key, which is what keeps
    the two paths within float reassociation of each other.
    """
    if not policy.quantize_updates:
        return g
    upd = hyper.lr * g
    if policy.stochastic and key is not None:
        updq = quantize_stochastic(upd, b_l["g_i"], b_l["g_f"], key)
    else:
        updq = quantize_ste(upd, b_l["g_i"], b_l["g_f"])
    upd = enabled * updq + (1.0 - enabled) * upd
    return upd / jnp.maximum(hyper.lr, 1e-20)


def _bits_xs(bits: BitSchedule) -> dict:
    """BitSchedule arrays as scan xs (leading dim = num units)."""
    return {"w_i": bits.w_i, "w_f": bits.w_f, "a_i": bits.a_i, "a_f": bits.a_f,
            "g_i": bits.g_i, "g_f": bits.g_f}


# ---------------------------------------------------------------------------
# Forward: scan saving quantized layer inputs (the X_i registers)
# ---------------------------------------------------------------------------

def forward_stack(body_fn: Callable, stacked: PyTree, shared: PyTree,
                  x0: Array, bits: BitSchedule, policy: QuantPolicy,
                  quantize_shared: bool = True):
    """body_fn(params_slice, shared, x, bits_layer) -> (y, aux).

    Returns (x_final, X_caches [L,...], aux_sum).  X_caches hold the
    *quantized* layer inputs — exactly what the backward pass re-linearises
    at, so forward and backward see identical numerics.

    ``quantize_shared=False`` for shared *activations* (e.g. encoder output
    feeding every decoder layer) which are quantized once by the caller.
    """
    enabled = bits.enabled

    def fwd(x, xs):
        p_l, b_l = xs
        if policy.quantize_acts:
            xq = (enabled * quantize_ste(x.astype(jnp.float32),
                                         b_l["a_i"], b_l["a_f"])
                  + (1.0 - enabled) * x.astype(jnp.float32)).astype(x.dtype)
        else:
            xq = x
        wq = quantize_weight_tree(p_l, b_l["w_i"], b_l["w_f"], enabled,
                                  policy.quantize_weights)
        sq = (quantize_weight_tree(shared, b_l["w_i"], b_l["w_f"], enabled,
                                   policy.quantize_weights)
              if quantize_shared else shared)
        y, aux = body_fn(wq, sq, xq, b_l)
        return y, (xq, aux)

    with jax.named_scope("block"):
        x_final, (caches, auxs) = xscan(fwd, x0, (stacked, _bits_xs(bits)))
        return x_final, caches, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# Backward: the G-chain reverse scan with fused per-layer update
# ---------------------------------------------------------------------------

def overlap_depth_for(policy: QuantPolicy, n_units: int) -> int:
    """Effective pipeline depth: ``policy.overlap_depth`` clamped to the
    layer count (a 2-layer stack can keep at most 2 reduces in flight)."""
    depth = int(policy.overlap_depth)
    if depth < 1:
        raise ValueError(
            f"QuantPolicy.overlap_depth must be >= 1, got {depth}")
    return min(depth, int(n_units))


def _dw_leaf_transports(policy: QuantPolicy, stacked: PyTree) -> list:
    """STATIC per-leaf transport decisions for one layer's dW tree (the
    [1:] slice shapes of ``stacked``, reduced as f32 like ``_vjp_layer``
    emits them).  Plain strings, so the overlapped paths can shape their
    program around them at trace time: ``"ring"`` leaves have genuinely
    in-flight hops worth deferring ``overlap_depth`` iterations, while
    blocking transports (``"psum"``/``"scatter"``) complete at start and
    get a same-iteration update."""
    slices = [jax.ShapeDtypeStruct(a.shape[1:], jnp.float32)
              for a in jax.tree.leaves(stacked)]
    return resolve_leaf_transports(
        slices, policy.dw_psum_axes, compressed=policy.compress_dw,
        num_replicas=policy.dw_num_replicas, transport=policy.dw_transport)


def _make_blocking_layer_update(policy: QuantPolicy, hyper: Hyper,
                                optim_cfg: OptimizerConfig, enabled: Array,
                                decisions: list):
    """Per-layer reduce + quantize + update when every dW leaf rides a
    BLOCKING transport (no ring hops to hide): the update lands in the
    same scan iteration, so the overlapped scan carries no pending state.

    Two refinements over the blocking off-path body make ``overlap=on``
    a measured win even where nothing can truly overlap (host-CPU device
    groups):

      * psum-decided leaves are FUSED into one variadic ``lax.psum`` —
        one rendezvous per layer instead of one per leaf;
      * scatter-decided leaves get the ZeRO-style SHARDED update when the
        optimizer is elementwise (sgd, no grad clip): reduce-scatter the
        dW leaf, run quantize-update + optimizer on this device's 1/g
        chunk only, and all-gather the UPDATED params — same wire bytes,
        1/g the update traffic (measured ~1.7x per leaf at dW sizes).
        Elementwise math on identical chunk values keeps the result
        within reduction-order reassociation of the fused psum path.

    The sharded leaves' grad-norm contribution is device-local (each
    device squares only its chunk), so callers must close the step with
    ``gsq += lax.psum(gsq_sharded, axes)`` — returned flag says whether
    that collective is needed.  Returns ``(update_layer, uses_sharded)``
    where ``update_layer(p_l, dW, opt_l, b_l, key) -> (new_p, new_opt,
    gsq, gsq_sharded)``.
    """
    axes = tuple(policy.dw_psum_axes)
    axis = axes if len(axes) > 1 else (axes[0] if axes else None)
    g = group_size(axes, policy.dw_num_replicas) if axes else 1
    # sharded-update eligibility is static: the optimizer and the update
    # quantizer must be elementwise so chunk results equal full-tensor
    # results per element (momentum8's rowwise absmax, the per-leaf clip
    # norm, and positional stochastic-rounding noise are not)
    sharded_ok = (bool(axes) and g > 1 and optim_cfg.kind == "sgd"
                  and optim_cfg.grad_clip == 0
                  and not policy.compress_dw
                  and not (policy.quantize_updates and policy.stochastic))
    sharded = [d == "scatter" and sharded_ok for d in decisions]
    uses_sharded = any(sharded)

    def update_layer(p_l, dW, opt_l, b_l, key):
        def qu(gg):
            return quantize_update(gg, b_l, key, enabled, policy, hyper)
        zero = jnp.float32(0.0)
        if not uses_sharded:
            # one fused blocking reduce + whole-tree update: the off
            # path's numerics, any optimizer
            leaves, treedef = jax.tree.flatten(dW)
            if policy.compress_dw:
                leaves = [compressed_psum(x, axes,
                                          num_replicas=policy.dw_num_replicas)
                          for x in leaves]
            elif axes:
                leaves = list(lax.psum(tuple(leaves), axes))
            leaves = [qu(x) for x in leaves]
            dWq = jax.tree.unflatten(treedef, leaves)
            new_p, new_opt = apply_update(p_l, dWq, opt_l, hyper, optim_cfg)
            gsq = sum(jnp.sum(jnp.square(x)) for x in leaves)
            return new_p, new_opt, gsq, zero
        p_leaves, ptd = jax.tree.flatten(p_l)
        g_leaves = jax.tree.leaves(dW)
        fuse = [i for i, s in enumerate(sharded) if not s]
        red = {}
        if fuse:
            reduced = (lax.psum(tuple(g_leaves[i] for i in fuse), axes)
                       if axes else [g_leaves[i] for i in fuse])
            red = dict(zip(fuse, reduced))
        new_leaves: list = [None] * len(p_leaves)
        gsq, gsq_sh = zero, zero
        for i, (pw, gw) in enumerate(zip(p_leaves, g_leaves)):
            if sharded[i]:
                chunk = qu(reduce_scatter_chunk(gw, axis, g))
                own = shard_chunk(pw, axis, g)
                new_chunk, _ = apply_update(own, chunk, {}, hyper, optim_cfg)
                new_leaves[i] = all_gather_chunks(new_chunk, axis, g,
                                                 tuple(pw.shape), pw.dtype)
                gsq_sh = gsq_sh + jnp.sum(jnp.square(chunk))
            else:
                gq = qu(red[i])
                new_leaves[i], _ = apply_update(pw, gq, {}, hyper, optim_cfg)
                gsq = gsq + jnp.sum(jnp.square(gq))
        # sgd is stateless (sharded_ok implies it): opt_l passes through
        return jax.tree.unflatten(ptd, new_leaves), opt_l, gsq, gsq_sh

    return scoped("update", update_layer), uses_sharded


def _overlapped_update_helpers(policy: QuantPolicy, hyper: Hyper,
                               optim_cfg: OptimizerConfig, enabled: Array,
                               key_for: Callable, depth: int):
    """Scaffolding of the ``depth``-deep software-pipelined per-layer dW
    reduce, shared by the overlapped backward scan and the stacked update
    tail (``apply_stacked_updates``) so the subtlest pieces exist exactly
    once.  The carry holds a tuple of ``depth`` pending entries, OLDEST
    first; each scan step starts one reduce and finalizes the oldest, so a
    layer's collective has ``depth`` layers' compute to hide behind:

    ``start``     issue a layer's all-reduce (dense or compressed, with the
                  policy's transport — autotuned by default)
    ``finalize``  wait on one in-flight entry, update-quantize, land the
                  delayed optimizer step; returns (new_p, new_opt, gsq)
    ``pending0``  warm-up carry: ``depth`` zero-slice entries with dummy
                  handles (no hops; finalizing one is a no-op update)
    ``drain``     finalize the ``depth`` entries still in flight after the
                  scan (oldest first); returns (flushes, gsq_sum)
    ``align``     undo the reverse scan's ``depth``-slot lag — ys slot i
                  holds the FINALIZED layer i+depth (the top ``depth``
                  slots warm-up garbage) and the drained layers
                  depth-1..0 are prepended in layer order
    """
    def start(dW, dummy=False):
        return tree_all_reduce_start(dW, policy.dw_psum_axes,
                                     compressed=policy.compress_dw,
                                     num_replicas=policy.dw_num_replicas,
                                     dummy=dummy,
                                     transport=policy.dw_transport)

    def finalize(pending):
        dW = tree_all_reduce_wait(pending["h"])
        key = key_for(pending["idx"])
        dW = jax.tree.map(
            lambda g: quantize_update(g, pending["bits"], key, enabled,
                                      policy, hyper), dW)
        new_p, new_opt = apply_update(pending["p"], dW, pending["opt"],
                                      hyper, optim_cfg)
        gsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(dW))
        return new_p, new_opt, gsq

    def slice0(tree, dtype=None):
        return jax.tree.map(
            lambda a: jnp.zeros(a.shape[1:], dtype or a.dtype), tree)

    def pending0(stacked, opt_stacked, bits_xs):
        entry = {"p": slice0(stacked), "opt": slice0(opt_stacked),
                 "h": start(slice0(stacked, jnp.float32), dummy=True),
                 "bits": slice0(bits_xs), "idx": jnp.int32(0)}
        return (entry,) * depth

    def drain(pending):
        flushes, gsq = [], jnp.float32(0.0)
        for entry in pending:       # oldest first: layers depth-1 .. 0
            new_p, new_opt, ginc = finalize(entry)
            flushes.append((new_p, new_opt))
            gsq = gsq + ginc
        return flushes, gsq

    def align(flushes, ys):
        # flushes arrive finalize-order (layer depth-1 first); stack them
        # in LAYER order and prepend to the ys slots that hold real layers
        stackf = jax.tree.map(lambda *fs: jnp.stack(list(fs)),
                              *reversed(flushes))
        return jax.tree.map(
            lambda f, y: jnp.concatenate([f, y[:-depth]], axis=0),
            stackf, ys)

    return tuple(scoped("update", f)
                 for f in (start, finalize, pending0, drain, align))


def backward_stack(body_fn: Callable, stacked: PyTree, shared: PyTree,
                   opt_stacked: PyTree, caches: PyTree, bits: BitSchedule,
                   G_out: Array, hyper: Hyper, policy: QuantPolicy,
                   optim_cfg: OptimizerConfig, aux_coef: float,
                   base_key: Optional[Array] = None,
                   quantize_shared: bool = True):
    """Reverse scan over layers.

    Per step (= paper steps 1-4 in one TDM frame):
      1. re-linearise the layer body at (q(W_i), q(X_i))   [VJP]
      2. dW_i, dShared_i, G_i  <- vjp(G_{i+1})
      3. G_i <- q(G_i)  (the low-bit backward signal sent upstream)
      4. W_i <- W_i - lr * dW_i  (fused update; DP all-reduce of dW_i is
         inside this scan body -> overlapped with step i-1's compute)

    With ``policy.overlap == "on"`` step 4's strategy follows the STATIC
    per-leaf transport decisions (``policy.dw_transport`` — autotuned by
    default, dist.async_collectives).  Ring-decided leaves have genuinely
    in-flight hops, so the whole layer tree is software-pipelined
    ``policy.overlap_depth`` scan steps deep: layer i STARTS its dW
    all-reduce and the update lands ``depth`` iterations later, the
    handles riding in the carry, so each collective overlaps ``depth``
    layers' VJP/G-step compute; the last ``depth`` in-flight layers are
    flushed after the scan.  When every leaf rides a BLOCKING transport
    (fused psum / native reduce-scatter) the reduce completes at start,
    so the update lands in the SAME iteration — one fused rendezvous per
    layer, and scatter-decided leaves run the optimizer on their 1/g
    chunk before all-gathering the updated params (the sharded update
    that makes ``overlap=on`` a measured win even on host-CPU groups
    where nothing can truly overlap).  With no ``dw_psum_axes`` both
    shapes degrade to the blocking one-device scan and the overlapped
    path computes bit-identical results — a pure schedule change.

    Gradient-scale convention: ``G_out`` arrives SCALED by policy.grad_scale
    (loss scaling for the low-bit chain).  dW is un-scaled just before the
    update; G and dShared stay in the scaled domain (callers un-scale when
    the gradient leaves the chain).

    Returns (G_in, new_stacked, new_opt, dShared_accum_SCALED, grad_sq_sum).
    """
    if policy.overlap not in ("off", "on"):
        raise ValueError(f"QuantPolicy.overlap must be 'off' or 'on', got "
                         f"{policy.overlap!r}")
    overlap = policy.overlap == "on"
    enabled = bits.enabled
    n_units = jax.tree.leaves(stacked)[0].shape[0]
    inv_scale = 1.0 / policy.grad_scale

    shared_f32 = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), shared)

    def _key_for(idx):
        return (jax.random.fold_in(base_key, idx)
                if (base_key is not None and policy.stochastic) else None)

    def _quant_update(g, b_l, key):
        return quantize_update(g, b_l, key, enabled, policy, hyper)

    def _vjp_layer(G, p_l, x_l, b_l):
        def f(pw, sw, xx):
            wq = quantize_weight_tree(pw, b_l["w_i"], b_l["w_f"], enabled,
                                      policy.quantize_weights)
            sq = (quantize_weight_tree(sw, b_l["w_i"], b_l["w_f"], enabled,
                                       policy.quantize_weights)
                  if quantize_shared else sw)
            return body_fn(wq, sq, xx, b_l)

        (y, aux), vjp = jax.vjp(f, p_l, shared, x_l)
        dW, dS, dX = vjp((G.astype(y.dtype),
                          jnp.asarray(aux_coef * policy.grad_scale,
                                      jnp.float32)))
        dW = jax.tree.map(lambda g: g.astype(jnp.float32) * inv_scale, dW)
        return dW, dS, dX

    if not overlap:
        def bwd(carry, xs):
            G, dshared_acc, gsq = carry
            p_l, opt_l, x_l, b_l, idx = xs
            dW, dS, dX = _vjp_layer(G, p_l, x_l, b_l)
            key = _key_for(idx)
            G_next = _quant_grad(dX, b_l["g_i"], b_l["g_f"], enabled, policy,
                                 key)

            def prep(g):
                if policy.compress_dw:
                    # per-layer dW through the int8 block-scaled wire format
                    # (and its all-reduce when mesh axes are named) — issued
                    # inside the scan body so it overlaps the next layer's
                    # G-step, the paper's timing overlap at pod scale
                    g = compressed_psum(g, policy.dw_psum_axes,
                                        num_replicas=policy.dw_num_replicas)
                elif policy.dw_psum_axes:
                    g = lax.psum(g, policy.dw_psum_axes)
                return _quant_update(g, b_l, key)
            with jax.named_scope("update"):
                dW = jax.tree.map(prep, dW)
                new_p, new_opt = apply_update(p_l, dW, opt_l, hyper,
                                              optim_cfg)
                gsq = gsq + sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(dW))
            dshared_acc = jax.tree.map(
                lambda a, d: a + d.astype(jnp.float32), dshared_acc, dS)
            return (G_next, dshared_acc, gsq), (new_p, new_opt)

        xs = (stacked, opt_stacked, caches, _bits_xs(bits),
              jnp.arange(n_units, dtype=jnp.int32))
        with jax.named_scope("gchain"):
            (G_in, dshared, gsq), (new_stacked, new_opt) = xscan(
                bwd, (G_out, shared_f32, jnp.float32(0.0)), xs, reverse=True)
        return G_in, new_stacked, new_opt, dshared, gsq

    # ---- communication-overlapped software pipeline ----------------------
    decisions = _dw_leaf_transports(policy, stacked)
    if "ring" not in decisions:
        # every dW leaf rides a BLOCKING transport: its reduce completes
        # at start, so deferring the update `depth` iterations buys no
        # overlap and only pays for it (pending-carry rotation, dummy
        # warm-up finalizes, drain realignment — measured ~10% of step
        # walltime).  Land each layer's update in the SAME iteration with
        # the fused-psum / sharded-scatter strategies instead.
        _update_layer, uses_sharded = _make_blocking_layer_update(
            policy, hyper, optim_cfg, enabled, decisions)

        def bwd(carry, xs):
            G, dshared_acc, gsq, gsq_sh = carry
            p_l, opt_l, x_l, b_l, idx = xs
            dW, dS, dX = _vjp_layer(G, p_l, x_l, b_l)
            key = _key_for(idx)
            G_next = _quant_grad(dX, b_l["g_i"], b_l["g_f"], enabled,
                                 policy, key)
            new_p, new_opt, ginc, ginc_sh = _update_layer(
                p_l, dW, opt_l, b_l, key)
            dshared_acc = jax.tree.map(
                lambda a, d: a + d.astype(jnp.float32), dshared_acc, dS)
            return (G_next, dshared_acc, gsq + ginc, gsq_sh + ginc_sh), \
                (new_p, new_opt)

        xs = (stacked, opt_stacked, caches, _bits_xs(bits),
              jnp.arange(n_units, dtype=jnp.int32))
        with jax.named_scope("gchain"):
            (G_in, dshared, gsq, gsq_sh), (new_stacked, new_opt) = xscan(
                bwd, (G_out, shared_f32, jnp.float32(0.0), jnp.float32(0.0)),
                xs, reverse=True)
        if uses_sharded:
            # sharded leaves squared only this device's chunk
            gsq = gsq + lax.psum(gsq_sh, policy.dw_psum_axes)
        return G_in, new_stacked, new_opt, dshared, gsq

    depth = overlap_depth_for(policy, n_units)
    _start, _finalize, _pending0, _drain, _align = _overlapped_update_helpers(
        policy, hyper, optim_cfg, enabled, _key_for, depth)

    def bwd(carry, xs):
        G, dshared_acc, gsq, pending = carry
        p_l, opt_l, x_l, b_l, idx = xs
        dW, dS, dX = _vjp_layer(G, p_l, x_l, b_l)
        G_next = _quant_grad(dX, b_l["g_i"], b_l["g_f"], enabled, policy,
                             _key_for(idx))
        # start layer i's reduce; land layer i+depth's (its hops overlapped
        # the last `depth` iterations' VJP compute)
        handles = _start(dW)
        fin_p, fin_opt, gsq_inc = _finalize(pending[0])
        pending_new = pending[1:] + ({"p": p_l, "opt": opt_l, "h": handles,
                                      "bits": b_l, "idx": idx},)
        dshared_acc = jax.tree.map(
            lambda a, d: a + d.astype(jnp.float32), dshared_acc, dS)
        return (G_next, dshared_acc, gsq + gsq_inc, pending_new), \
            (fin_p, fin_opt)

    xs = (stacked, opt_stacked, caches, _bits_xs(bits),
          jnp.arange(n_units, dtype=jnp.int32))
    with jax.named_scope("gchain"):
        (G_in, dshared, gsq, pending), (fin_stacked, fin_opt) = xscan(
            bwd, (G_out, shared_f32, jnp.float32(0.0),
                  _pending0(stacked, opt_stacked, _bits_xs(bits))), xs,
            reverse=True)
    # drain: layers depth-1..0's reduces are still in flight after the scan
    flushes, gsq_f = _drain(pending)
    return (G_in, _align([f[0] for f in flushes], fin_stacked),
            _align([f[1] for f in flushes], fin_opt), dshared, gsq + gsq_f)


# ---------------------------------------------------------------------------
# Stacked-dW update tail (the stage-sharded pipeline path)
# ---------------------------------------------------------------------------

def apply_stacked_updates(stacked: PyTree, dW: PyTree, opt_stacked: PyTree,
                          bits: BitSchedule, hyper: Hyper,
                          policy: QuantPolicy, optim_cfg: OptimizerConfig,
                          base_key: Optional[Array] = None):
    """Reduce + quantize + apply per-layer updates of a fully materialised
    stacked dW tree — the update tail of the stage-sharded pipeline path,
    where ``jax.vjp`` through ``dist.pipeline`` hands back all layers' dW
    at once instead of one layer per reverse-scan step.

    Per layer (mirroring ``backward_stack``'s fused step 4, same order and
    same per-layer PRNG keys, so both paths agree to float reassociation):
    the dW leaves go through ``compressed_psum`` (``policy.compress_dw``)
    or a dense ``lax.psum`` over ``policy.dw_psum_axes`` — composing the
    pipe axis with the data axis — then ``quantize_update`` (strict-paper
    ``q(alpha*dW)``), then the optimizer.

    ``policy.overlap == "off"``: one vmap over the layer axis.
    ``policy.overlap == "on"``: identical in structure to the overlapped
    backward scan — ring-decided leaves ride a reverse scan whose
    per-layer reduce is software-pipelined ``policy.overlap_depth`` steps
    deep (start layer i's reduce, land layer i+depth's while its hops
    overlap this step's update compute); when every leaf's transport is
    blocking the updates land same-iteration with the fused-psum /
    sharded-scatter strategies instead.  With no ``dw_psum_axes`` the
    reduces are identities and the results are bitwise equal to the
    vmapped path.

    Returns ``(new_stacked, new_opt, grad_sq_sum)``.
    """
    enabled = bits.enabled
    n_units = jax.tree.leaves(stacked)[0].shape[0]
    bxs = _bits_xs(bits)
    idxs = jnp.arange(n_units, dtype=jnp.int32)

    def _key_for(idx):
        return (jax.random.fold_in(base_key, idx)
                if (base_key is not None and policy.stochastic) else None)

    if policy.overlap != "on":
        def upd(p_l, g_l, s_l, b_l, idx):
            key = _key_for(idx)

            def prep(g):
                if policy.compress_dw:
                    g = compressed_psum(g, policy.dw_psum_axes,
                                        num_replicas=policy.dw_num_replicas)
                elif policy.dw_psum_axes:
                    g = lax.psum(g, policy.dw_psum_axes)
                return quantize_update(g, b_l, key, enabled, policy, hyper)

            g_l = jax.tree.map(prep, g_l)
            new_p, new_s = apply_update(p_l, g_l, s_l, hyper, optim_cfg)
            gsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(g_l))
            return new_p, new_s, gsq

        new_p, new_s, gsqs = jax.vmap(scoped("update", upd))(
            stacked, dW, opt_stacked, bxs, idxs)
        return new_p, new_s, jnp.sum(gsqs)

    decisions = _dw_leaf_transports(policy, stacked)
    if "ring" not in decisions:
        # all-blocking transports: same-iteration updates (see
        # backward_stack) — a reverse scan to keep the layer-major
        # collective order identical to the overlapped backward scan
        _update_layer, uses_sharded = _make_blocking_layer_update(
            policy, hyper, optim_cfg, enabled, decisions)

        def body(carry, xs):
            gsq, gsq_sh = carry
            p_l, g_l, s_l, b_l, idx = xs
            new_p, new_s, ginc, ginc_sh = _update_layer(
                p_l, g_l, s_l, b_l, _key_for(idx))
            return (gsq + ginc, gsq_sh + ginc_sh), (new_p, new_s)

        xs = (stacked, dW, opt_stacked, bxs, idxs)
        (gsq, gsq_sh), (new_p, new_s) = xscan(
            body, (jnp.float32(0.0), jnp.float32(0.0)), xs, reverse=True)
        if uses_sharded:
            gsq = gsq + lax.psum(gsq_sh, policy.dw_psum_axes)
        return new_p, new_s, gsq

    depth = overlap_depth_for(policy, n_units)
    _start, _finalize, _pending0, _drain, _align = _overlapped_update_helpers(
        policy, hyper, optim_cfg, enabled, _key_for, depth)

    def body(carry, xs):
        gsq, pending = carry
        p_l, g_l, s_l, b_l, idx = xs
        handles = _start(g_l)
        fin_p, fin_s, ginc = _finalize(pending[0])
        pending_new = pending[1:] + ({"p": p_l, "opt": s_l, "h": handles,
                                      "bits": b_l, "idx": idx},)
        return (gsq + ginc, pending_new), (fin_p, fin_s)

    xs = (stacked, dW, opt_stacked, bxs, idxs)
    (gsq, pending), (fin_p, fin_s) = xscan(
        body, (jnp.float32(0.0), _pending0(stacked, opt_stacked, bxs)), xs,
        reverse=True)
    # drain + re-align exactly like the overlapped backward scan above
    flushes, gsq_f = _drain(pending)
    return (_align([f[0] for f in flushes], fin_p),
            _align([f[1] for f in flushes], fin_s), gsq + gsq_f)
