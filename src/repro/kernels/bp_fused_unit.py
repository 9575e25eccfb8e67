"""bp_fused_unit: the paper's full TDM frame as ONE kernel pass.

TaxoNN time-multiplexes four slots of the SGD unit onto the inference PE
array; per layer i the frame is:

    G_{i-1} = q_g( (G_i @ q_w(W_i)^T) * f'(Z_{i-1}) )      (Eq. 8)
    dW_i    = X_{i-1}^T @ G_i                              (Eq. 9)
    W_i    <- q_w'( W_i - lr * dW_i )                      (Eq. 1, step 4)

This kernel runs all three in a single ``pallas_call``: one pass over the
token dimension streams G/X/Z blocks through VMEM while W stays resident,
so G_out, dW and W_new share every operand fetch — the fused-update
property (gradient lifetime = one PE pass) with zero HBM round-trips for
the intermediates.

Layout: grid (T/bt,) over token blocks only; W [Din, Dout] and the dW
accumulator are VMEM-resident for the whole frame (sized for the paper's
layer shapes — the autotuner in ops.py falls back to the sequential
kernels when Din*Dout exceeds the VMEM budget).  Per step t:

  * G_out block [bt, Din] = (G block @ W^T) * f'(Z block)   (written out)
  * dW accumulator += X block^T @ G block
  * at the last step: W_new = W - lr * dW                  (written out)

Datapaths: ``emulate`` (f32 MACs, in-kernel kq of W for the G product) and
``int8`` (G/X int8 payloads, W quantized to int8 in-kernel from its static
(I,F) spec; both MACs run int8 x int8 -> int32 with exact wide
accumulators; scales applied once per output).

``double_buffer=True`` streams the three token-block operands (G, X, Z)
HBM -> 2-slot VMEM scratch with explicit prefetch DMAs: frame step k waits
the copies started at step k-1 and starts step k+1's, so the next frame's
operands ride the DMA while the PEs run the current frame's three TDM
slots — the paper's Fig. 3 overlap realised at the memory system.  W stays
VMEM-resident either way.  Numerics identical.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import act_deriv, db_step, int8_dot, maybe_kq
from repro.quant.int8 import int8_spec

# G block [bt, Dout] @ (W [Din, Dout])^T -> [bt, Din]
_GW_DIMS = (((1,), (1,)), ((), ()))
# (X block [bt, Din])^T @ G block [bt, Dout] -> [Din, Dout]
_XG_DIMS = (((0,), (0,)), ((), ()))


def _kernel(g_ref, w_ref, x_ref, z_ref, lr_ref, go_ref, wo_ref, acc_ref,
            wq_ref, *, n_k: int, g_bits, w_bits, w_out_bits, act: str):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # quantize the resident W once per frame (loop-invariant)
        wq_ref[...] = maybe_kq(w_ref[...].astype(jnp.float32), w_bits)

    g = g_ref[...].astype(jnp.float32)

    go = jax.lax.dot_general(g, wq_ref[...], _GW_DIMS,   # backward uses q_w(W)
                             preferred_element_type=jnp.float32)
    go = go * act_deriv(z_ref[...].astype(jnp.float32), act)
    go_ref[...] = maybe_kq(go, g_bits)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), g, _XG_DIMS,
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        w = w_ref[...].astype(jnp.float32)               # master
        wo_ref[...] = maybe_kq(w - lr_ref[0] * acc_ref[...], w_out_bits)


def _quantize_resident_w(w, wq_ref, sw_ref, w_spec_static):
    """Master W -> int8 payload, once per frame (loop-invariant): on its
    (I,F)-derived grid when the format embeds (``w_spec_static``), else
    absmax over the resident W (block-scaled transport of a too-wide
    format).  s_w is kept as a (1, 1) vector: Mosaic stores no scalar to
    VMEM."""
    if w_spec_static is not None:
        s_w = jnp.full((1, 1), w_spec_static.scale, jnp.float32)
        wq_ref[...] = jnp.clip(jnp.round(w / s_w), w_spec_static.qmin,
                               w_spec_static.qmax).astype(jnp.int8)
    else:
        am = jnp.max(jnp.abs(w), keepdims=True)
        s_w = jnp.where(am > 0, am / 127.0, jnp.float32(1.0))
        wq_ref[...] = jnp.clip(jnp.round(w / s_w), -127,
                               127).astype(jnp.int8)
    sw_ref[...] = s_w


def _kernel_int8(g_ref, w_ref, x_ref, z_ref, meta_ref, go_ref, wo_ref,
                 acc_ref, wq_ref, sw_ref, *, n_k: int, g_bits, w_bits,
                 w_out_bits, act: str, w_spec_static):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        w = w_ref[...].astype(jnp.float32)
        _quantize_resident_w(w, wq_ref, sw_ref, w_spec_static)

    go = (int8_dot(g_ref[...], wq_ref[...], _GW_DIMS).astype(jnp.float32)
          * (meta_ref[0] * sw_ref[...]))              # s_g * s_w
    go = go * act_deriv(z_ref[...].astype(jnp.float32), act)
    go_ref[...] = maybe_kq(go, g_bits)

    acc_ref[...] += int8_dot(x_ref[...], g_ref[...], _XG_DIMS)

    @pl.when(k == n_k - 1)
    def _finish():
        dw = acc_ref[...].astype(jnp.float32) * meta_ref[1]   # s_x * s_g
        wo_ref[...] = maybe_kq(w_ref[...].astype(jnp.float32)
                               - meta_ref[2] * dw, w_out_bits)


def _db_dmas(g_hbm, x_hbm, z_hbm, gbuf, xbuf, zbuf, sem, bt):
    """Token-block DMA constructors (full-width rows [kk*bt, kk*bt+bt))."""
    def dma(hbm, buf, slot, kk, op):
        return pltpu.make_async_copy(
            hbm.at[pl.ds(kk * bt, bt), :], buf.at[slot], sem.at[op, slot])

    return (lambda s, kk: dma(g_hbm, gbuf, s, kk, 0),
            lambda s, kk: dma(x_hbm, xbuf, s, kk, 1),
            lambda s, kk: dma(z_hbm, zbuf, s, kk, 2))


def _kernel_db(g_hbm, w_ref, x_hbm, z_hbm, lr_ref, go_ref, wo_ref, gbuf,
               xbuf, zbuf, acc_ref, wq_ref, sem, *, n_k: int, bt: int,
               g_bits, w_bits, w_out_bits, act: str):
    k = pl.program_id(0)
    dmas = _db_dmas(g_hbm, x_hbm, z_hbm, gbuf, xbuf, zbuf, sem, bt)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        wq_ref[...] = maybe_kq(w_ref[...].astype(jnp.float32), w_bits)

    slot = db_step(k, n_k, dmas)
    g = gbuf[slot].astype(jnp.float32)

    go = jax.lax.dot_general(g, wq_ref[...], _GW_DIMS,
                             preferred_element_type=jnp.float32)
    go = go * act_deriv(zbuf[slot].astype(jnp.float32), act)
    go_ref[...] = maybe_kq(go, g_bits)

    acc_ref[...] += jax.lax.dot_general(
        xbuf[slot].astype(jnp.float32), g, _XG_DIMS,
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        w = w_ref[...].astype(jnp.float32)
        wo_ref[...] = maybe_kq(w - lr_ref[0] * acc_ref[...], w_out_bits)


def _kernel_db_int8(g_hbm, w_ref, x_hbm, z_hbm, meta_ref, go_ref, wo_ref,
                    gbuf, xbuf, zbuf, acc_ref, wq_ref, sw_ref, sem, *,
                    n_k: int, bt: int, g_bits, w_bits, w_out_bits, act: str,
                    w_spec_static):
    k = pl.program_id(0)
    dmas = _db_dmas(g_hbm, x_hbm, z_hbm, gbuf, xbuf, zbuf, sem, bt)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        w = w_ref[...].astype(jnp.float32)
        _quantize_resident_w(w, wq_ref, sw_ref, w_spec_static)

    slot = db_step(k, n_k, dmas)

    go = (int8_dot(gbuf[slot], wq_ref[...], _GW_DIMS).astype(jnp.float32)
          * (meta_ref[0] * sw_ref[...]))
    go = go * act_deriv(zbuf[slot].astype(jnp.float32), act)
    go_ref[...] = maybe_kq(go, g_bits)

    acc_ref[...] += int8_dot(xbuf[slot], gbuf[slot], _XG_DIMS)

    @pl.when(k == n_k - 1)
    def _finish():
        dw = acc_ref[...].astype(jnp.float32) * meta_ref[1]
        wo_ref[...] = maybe_kq(w_ref[...].astype(jnp.float32)
                               - meta_ref[2] * dw, w_out_bits)


def bp_fused_unit(g: jax.Array, w: jax.Array, x: jax.Array, z: jax.Array,
                  lr, *, g_bits=(2, 12), w_bits=(2, 12), w_out_bits=None,
                  act: str = "relu", bt: int = 128,
                  interpret: bool = False,
                  datapath: str = "emulate",
                  g_scale: Optional[jax.Array] = None,
                  x_scale: Optional[jax.Array] = None,
                  double_buffer: bool = False):
    """One TDM frame.  g: [T, Dout] (dE/dZ_i); w: [Din, Dout] f32 master;
    x: [T, Din] (layer input X_{i-1}); z: [T, Din] (upstream pre-activation).

    Returns (G_out [T, Din] f32, W_new [Din, Dout] f32).

    int8 datapath: g/x are int8 payloads with scales (g_scale, x_scale);
    w stays the f32 master and is re-quantized to int8 in-kernel from the
    static ``w_bits`` format for the G product.
    double_buffer: explicit 2-slot DMA prefetch of the G/X/Z token blocks.
    """
    t, dout = g.shape
    din, dout2 = w.shape
    assert dout == dout2 and x.shape == (t, din) and z.shape == (t, din)
    bt = min(bt, t)
    assert t % bt == 0, (t, bt)
    n_k = t // bt

    grid = (n_k,)
    g_spec = pl.BlockSpec((bt, dout), lambda k: (k, 0))
    w_spec = pl.BlockSpec((din, dout), lambda k: (0, 0))
    x_spec = pl.BlockSpec((bt, din), lambda k: (k, 0))
    z_spec = pl.BlockSpec((bt, din), lambda k: (k, 0))
    go_spec = pl.BlockSpec((bt, din), lambda k: (k, 0))
    wo_spec = pl.BlockSpec((din, dout), lambda k: (0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars read in-body
    out_shape = [jax.ShapeDtypeStruct((t, din), jnp.float32),
                 jax.ShapeDtypeStruct((din, dout), jnp.float32)]
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    if double_buffer:
        # slots keep each operand's own dtype; the kernel bodies cast where
        # the implicit-pipeline kernels do, so numerics match exactly
        db_scratch = [pltpu.VMEM((2, bt, dout), g.dtype),   # G slots
                      pltpu.VMEM((2, bt, din), x.dtype),    # X slots
                      pltpu.VMEM((2, bt, din), z.dtype)]    # Z slots
        db_sem = [pltpu.SemaphoreType.DMA((3, 2))]

    if datapath == "int8":
        assert g.dtype == jnp.int8 and x.dtype == jnp.int8, (g.dtype, x.dtype)
        assert g_scale is not None and x_scale is not None
        # W embeds on its static (I,F) grid only when that fits int8; a
        # wider/absent format uses in-kernel absmax (block-scaled transport)
        spec = int8_spec(*w_bits) if w_bits is not None else None
        if spec is not None and not spec.exact:
            spec = None
        g_s = jnp.asarray(g_scale, jnp.float32)
        x_s = jnp.asarray(x_scale, jnp.float32)
        meta = jnp.stack([g_s,                             # s_g (s_w in-kernel)
                          x_s * g_s,                       # dW scale
                          jnp.asarray(lr, jnp.float32)])
        if double_buffer:
            return pl.pallas_call(
                functools.partial(_kernel_db_int8, n_k=n_k, bt=bt,
                                  g_bits=g_bits, w_bits=w_bits,
                                  w_out_bits=w_out_bits, act=act,
                                  w_spec_static=spec),
                grid=grid,
                in_specs=[any_spec, w_spec, any_spec, any_spec, smem_spec],
                out_specs=[go_spec, wo_spec],
                out_shape=out_shape,
                scratch_shapes=db_scratch
                + [pltpu.VMEM((din, dout), jnp.int32),
                   pltpu.VMEM((din, dout), jnp.int8),
                   pltpu.VMEM((1, 1), jnp.float32)] + db_sem,
                compiler_params=params, interpret=interpret,
                name="bp_fused_unit",
            )(g, w, x, z, meta)
        return pl.pallas_call(
            functools.partial(_kernel_int8, n_k=n_k, g_bits=g_bits,
                              w_bits=w_bits, w_out_bits=w_out_bits, act=act,
                              w_spec_static=spec),
            grid=grid,
            in_specs=[g_spec, w_spec, x_spec, z_spec, smem_spec],
            out_specs=[go_spec, wo_spec],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((din, dout), jnp.int32),
                            pltpu.VMEM((din, dout), jnp.int8),
                            pltpu.VMEM((1, 1), jnp.float32)],
            compiler_params=params, interpret=interpret,
            name="bp_fused_unit",
        )(g, w, x, z, meta)

    assert datapath == "emulate", datapath
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    if double_buffer:
        return pl.pallas_call(
            functools.partial(_kernel_db, n_k=n_k, bt=bt, g_bits=g_bits,
                              w_bits=w_bits, w_out_bits=w_out_bits, act=act),
            grid=grid,
            in_specs=[any_spec, w_spec, any_spec, any_spec, smem_spec],
            out_specs=[go_spec, wo_spec],
            out_shape=out_shape,
            scratch_shapes=db_scratch
            + [pltpu.VMEM((din, dout), jnp.float32),
               pltpu.VMEM((din, dout), jnp.float32)] + db_sem,
            compiler_params=params, interpret=interpret,
            name="bp_fused_unit",
        )(g, w, x, z, lr_arr)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, g_bits=g_bits, w_bits=w_bits,
                          w_out_bits=w_out_bits, act=act),
        grid=grid,
        in_specs=[g_spec, w_spec, x_spec, z_spec, smem_spec],
        out_specs=[go_spec, wo_spec],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((din, dout), jnp.float32),
                        pltpu.VMEM((din, dout), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="bp_fused_unit",
    )(g, w, x, z, lr_arr)
