"""Fused decode-prologue kernel: RMSNorm + QKV projection (+ biases) in
one ``pallas_call`` — one HBM round-trip for the normed residual.

The unfused decode prologue (``models.layers.apply_norm`` then
``_project_qkv``) writes the normed residual back to HBM and re-reads it
for each of the three projections — exactly the per-layer data-flow
staging TaxoNN's time-multiplexed frame collapses.  Here ONE grid step
takes the whole slot batch: decode rows are [B, D] with small B (the slot
count), so batching them into a single VMEM-resident matmul frame uses
the MXU where B row-at-a-time gemvs would not — the body norms all
residual rows, runs the three projections against the resident QKV
weights and adds the biases.  Rows stay 2D ([B, heads*hd]): Mosaic does
not split the lane dim into (heads, hd) in-kernel, so RoPE runs after the
kernel through ``models.layers.apply_rope`` itself (v is never rope'd,
matching ``_project_qkv``).

The math is op-for-op the unfused path's (rmsnorm formula, dt-cast
weights), shared between the kernel body and the jitted ``_ref`` fallback
at the same batched shapes — same ops at the same shapes is what makes
kernel and ref BITWISE identical in interpret mode (a [1, D] row-at-a-time
dot would round differently from the batched dot), and both bitwise
identical to ``apply_norm`` + ``_project_qkv`` under jit (tested in
tests/test_decode_prologue).

The int8 datapath variant rides ``quant/int8.py``'s grid: weights carry
per-tensor absmax scales (quantized once outside the call, read in-kernel
from SMEM), the normed activation row is quantized per-row, the MACs run
int8 x int8 -> int32 (``common.int8_dot``), and one rescale lands the dt
output before the biases.  Its contract is bitwise vs ``_ref_int8`` (not
vs the f32 path).

``decode_prologue`` picks kernel vs ref with ``ops.tune_prologue``: the
kernel when the weight-resident VMEM budget admits the model's head
geometry, the jnp fallback otherwise — semantics identical either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kops
from repro.kernels.common import int8_dot
from repro.models import layers as L
from repro.quant.int8 import quantize_int8, quantize_int8_absmax


# ---------------------------------------------------------------------------
# Shared row math — the bitwise contract between kernel body and ref
# ---------------------------------------------------------------------------

def _rms_rows(x2, nscale, eps: float):
    """Row-wise rmsnorm, op-for-op ``models.layers.rmsnorm``.  x2: [R, D];
    nscale: [1, D] f32 (the norm's scale param)."""
    dtype = x2.dtype
    xf = x2.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * nscale).astype(dtype)


def _add_biases(q, k, v, biases):
    if biases is None:
        return q, k, v
    dt = q.dtype
    bq, bk, bv = biases
    return q + bq.astype(dt), k + bk.astype(dt), v + bv.astype(dt)


def _prologue_rows(x2, nscale, wq2, wk2, wv2, biases, *, eps: float):
    """norm -> 3 projections -> biases over R token rows.  Weights arrive
    2D ([D, H*hd]) and are dt-cast exactly like ``_project_qkv``; biases
    are [1, H*hd] rows.  Returns 2D rows."""
    dt = x2.dtype
    xn = _rms_rows(x2, nscale, eps)

    def proj(w2):
        return jnp.dot(xn, w2.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return _add_biases(proj(wq2), proj(wk2), proj(wv2), biases)


def _prologue_rows_int8(x2, nscale, qwq, qwk, qwv, wscales, biases, *,
                        eps: float):
    """Int8 datapath: per-row absmax quant of the normed activation, int32
    MACs against the per-tensor-scaled int8 weights (``wscales`` holds the
    three weight scales), one rescale."""
    dt = x2.dtype
    xn = _rms_rows(x2, nscale, eps)
    xf = xn.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)        # [R, 1]
    sx = jnp.where(amax > 0, amax / 127.0, jnp.float32(1.0))
    qx = quantize_int8(xf, sx)

    def proj(qw, sw):
        acc = int8_dot(qx, qw).astype(jnp.float32)
        return (acc * (sx * sw)).astype(dt)
    return _add_biases(proj(qwq, wscales[0]), proj(qwk, wscales[1]),
                       proj(qwv, wscales[2]), biases)


# ---------------------------------------------------------------------------
# Kernel body (one grid step for the whole slot batch)
# ---------------------------------------------------------------------------

def _kernel(x_ref, ns_ref, wq_ref, wk_ref, wv_ref, *rest, int8: bool,
            qkv_bias: bool, eps: float):
    # ONE grid step for the whole slot batch: decode rows are [B, D] with
    # small B (the slot count), so batching them into a single MXU matmul
    # frame beats B separate gemvs — and running the ref's exact batched op
    # sequence is what keeps kernel and ref BITWISE identical (a [1, D]
    # row-at-a-time dot rounds differently from the batched dot).
    *ins, oq_ref, ok_ref, ov_ref = rest
    biases = tuple(r[...] for r in ins[:3]) if qkv_bias else None
    if int8:
        ws_ref = ins[-1]                                      # SMEM scalars
        q, k, v = _prologue_rows_int8(
            x_ref[...], ns_ref[...], wq_ref[...], wk_ref[...], wv_ref[...],
            (ws_ref[0], ws_ref[1], ws_ref[2]), biases, eps=eps)
    else:
        q, k, v = _prologue_rows(
            x_ref[...], ns_ref[...], wq_ref[...], wk_ref[...], wv_ref[...],
            biases, eps=eps)
    oq_ref[...] = q
    ok_ref[...] = k
    ov_ref[...] = v


# ---------------------------------------------------------------------------
# jnp fallbacks — the same row math batched over all slots
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps",))
def _ref(x2, nscale, wq2, wk2, wv2, biases, *, eps: float):
    return _prologue_rows(x2, nscale, wq2, wk2, wv2, biases, eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ref_int8(x2, nscale, qwq, qwk, qwv, wscales, biases, *, eps: float):
    return _prologue_rows_int8(x2, nscale, qwq, qwk, qwv, wscales, biases,
                               eps=eps)


def _call_kernel(x2, nscale, wq2, wk2, wv2, wscales, biases, *, int8: bool,
                 eps: float):
    b, d = x2.shape
    dt = x2.dtype

    def full(x):
        nd = len(x.shape)
        return pl.BlockSpec(x.shape, lambda i, _nd=nd: (0,) * _nd)

    args = [x2, nscale, wq2, wk2, wv2] + list(biases or ())
    in_specs = [full(a) for a in args]
    if int8:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(wscales)
    out_shape = [jax.ShapeDtypeStruct((b, w.shape[1]), dt)
                 for w in (wq2, wk2, wv2)]
    body = functools.partial(_kernel, int8=int8, qkv_bias=biases is not None,
                             eps=eps)
    return pl.pallas_call(
        body,
        grid=(1,),
        in_specs=in_specs,
        out_specs=[full(o) for o in out_shape],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kops.interpret_mode("decode_prologue"),
        name="decode_prologue",
    )(*args)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def prologue_supported(cfg) -> bool:
    """Head geometries the fused prologue covers: rmsnorm front (layernorm
    archs keep the unfused path), standard GQA/MHA heads (no MLA latent
    projections), lane-aligned head dim."""
    return (cfg.norm_kind == "rmsnorm" and not cfg.use_mla
            and cfg.num_heads > 0 and cfg.head_dim % 8 == 0
            and cfg.d_model % 8 == 0)


def prologue_active(cfg, x) -> bool:
    """Whether the decode step should ride the fused prologue: supported
    geometry, the kernel datapath enabled (``KernelBackend`` != off), and a
    single-token row (prefill chunks keep the batched unfused path)."""
    return (prologue_supported(cfg) and kops.current_backend() != "off"
            and x.shape[1] == 1)


def decode_prologue(norm_params, attn_params, x, cfg, positions):
    """Fused RMSNorm + QKV (+ biases), then rope, for one decode token per
    slot.

    x: [B, 1, D] residual stream; positions: [B] int32 (each slot's
    absolute token position); norm/attn params are the block's unfused
    parameter dicts (weights are reshaped, never copied out of the tree).
    Returns (q [B,1,H,hd], k [B,1,Hkv,hd], v [B,1,Hkv,hd]) — exactly what
    ``apply_norm`` + ``_project_qkv`` produce.
    """
    b, t, d = x.shape
    assert t == 1, x.shape
    wq, wk, wv = attn_params["wq"], attn_params["wk"], attn_params["wv"]
    _, h, hd = wq.shape
    hkv = wk.shape[1]
    wq2 = wq.reshape(d, h * hd)
    wk2 = wk.reshape(d, hkv * hd)
    wv2 = wv.reshape(d, hkv * hd)
    nscale = norm_params["scale"].reshape(1, d)
    biases = None
    if cfg.qkv_bias:
        biases = tuple(attn_params[n].reshape(1, -1)
                       for n in ("bq", "bk", "bv"))
    x2 = x[:, 0, :]
    eps = float(cfg.norm_eps)

    int8 = kops.current_backend() == "int8"
    itemsize = 1 if int8 else x.dtype.itemsize
    fits = kops.tune_prologue(d, h, hkv, hd, itemsize=itemsize)
    if fits is None:
        kops.note_path("decode_prologue", "over_vmem")
    if int8:
        qwq, swq = quantize_int8_absmax(wq2)
        qwk, swk = quantize_int8_absmax(wk2)
        qwv, swv = quantize_int8_absmax(wv2)
        wscales = jnp.stack([swq, swk, swv])
        if fits is None:
            q, k, v = _ref_int8(x2, nscale, qwq, qwk, qwv, wscales, biases,
                                eps=eps)
        else:
            q, k, v = _call_kernel(x2, nscale, qwq, qwk, qwv, wscales,
                                   biases, int8=True, eps=eps)
    else:
        if fits is None:
            q, k, v = _ref(x2, nscale, wq2, wk2, wv2, biases, eps=eps)
        else:
            q, k, v = _call_kernel(x2, nscale, wq2, wk2, wv2, None, biases,
                                   int8=False, eps=eps)
    return rows_to_heads(q, k, v, cfg, positions)


def rows_to_heads(q, k, v, cfg, positions):
    """[B, heads*hd] prologue rows -> [B, 1, heads, hd], q/k rope'd at each
    slot's position (v never is, matching ``_project_qkv``)."""
    b = q.shape[0]
    hd = cfg.head_dim
    q, k, v = (r.reshape(b, 1, -1, hd) for r in (q, k, v))
    if cfg.use_rope:
        pos = positions.astype(jnp.int32)[:, None]
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
    return q, k, v
