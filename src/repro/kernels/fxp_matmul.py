"""fxp_matmul: fixed-point (I,F) quantized matmul + fused activation.

The TaxoNN PE datapath's forward op: y = f(q_a(X) @ q_w(W)).  Two datapaths
share one tiling:

  * ``datapath="emulate"`` — the MAC emulated at f32 with in-kernel (I,F)
    round-to-nearest (kq) and a f32 accumulator.  This is the CPU/interpret
    reference path and the pre-int8 behaviour.
  * ``datapath="int8"``    — X and W arrive as int8 payloads (the
    block-scaled storage format of ``repro.quant.int8``); the MAC runs as
    ``dot(int8, int8) -> int32`` on the MXU with an exact int32 VMEM
    accumulator (the paper's wide accumulator registers), and the combined
    scale ``s_x * s_w`` is applied once at the final k step — followed by
    the fused activation and optional output re-quantization.

Tiling: grid (M/bm, N/bn, K/bk); X block [bm,bk] and W block [bk,bn] live
in VMEM; the [bm,bn] accumulator lives across the k steps (revisiting
semantics: k is the innermost, "arbitrary" dimension).  Block defaults are
MXU-aligned (multiples of 128 on the contracted dims).

``double_buffer=True`` switches the operand fetch to an EXPLICIT
double-buffered DMA datapath (NeuroTrainer's memory/compute overlap at the
kernel level): X and W stay in HBM (``memory_space=ANY``) and each grid
step k prefetches block k+1 into the second slot of a 2-deep VMEM scratch
while the MXU consumes slot k%2 — the DMA started at step k is waited at
step k+1, one grid step of overlap per operand block.  Numerics are
IDENTICAL to the implicit-pipeline path (same blocks, same MAC order);
``kernels.ops.tune_blocks(double_buffer=True)`` budgets the 2x VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import act_fn, db_step, int8_dot, maybe_kq


def _kernel(x_ref, w_ref, o_ref, *, n_k: int, xa_bits, w_bits, out_bits,
            act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = maybe_kq(x_ref[...].astype(jnp.float32), xa_bits)
    wq = maybe_kq(w_ref[...].astype(jnp.float32), w_bits)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _finish():
        y = act_fn(o_ref[...], act)
        y = maybe_kq(y, out_bits)
        o_ref[...] = y


def _kernel_int8(x_ref, w_ref, meta_ref, o_ref, acc_ref, *, n_k: int,
                 out_bits, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(x_ref[...], w_ref[...])

    @pl.when(k == n_k - 1)
    def _finish():
        # one rescale out of the wide accumulator, then the fused activation
        y = act_fn(acc_ref[...].astype(jnp.float32) * meta_ref[0], act)
        y = maybe_kq(y, out_bits)
        o_ref[...] = y


def _db_dmas(x_hbm, w_hbm, xbuf, wbuf, sem, bm, bn, bk):
    """Block-(i,·,·)/(·,j,·) DMA constructors for the double-buffered path."""
    i, j = pl.program_id(0), pl.program_id(1)

    def dma_x(slot, kk):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
            xbuf.at[slot], sem.at[0, slot])

    def dma_w(slot, kk):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)],
            wbuf.at[slot], sem.at[1, slot])

    return (dma_x, dma_w)


def _kernel_db(x_hbm, w_hbm, o_ref, xbuf, wbuf, sem, *, n_k: int,
               bm: int, bn: int, bk: int, xa_bits, w_bits, out_bits,
               act: str):
    k = pl.program_id(2)
    dmas = _db_dmas(x_hbm, w_hbm, xbuf, wbuf, sem, bm, bn, bk)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot = db_step(k, n_k, dmas)           # next block rides the DMA while
    xq = maybe_kq(xbuf[slot].astype(jnp.float32), xa_bits)  # MXU eats this one
    wq = maybe_kq(wbuf[slot].astype(jnp.float32), w_bits)
    o_ref[...] += jnp.dot(xq, wq, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        y = act_fn(o_ref[...], act)
        o_ref[...] = maybe_kq(y, out_bits)


def _kernel_db_int8(x_hbm, w_hbm, meta_ref, o_ref, xbuf, wbuf, acc_ref, sem,
                    *, n_k: int, bm: int, bn: int, bk: int, out_bits,
                    act: str):
    k = pl.program_id(2)
    dmas = _db_dmas(x_hbm, w_hbm, xbuf, wbuf, sem, bm, bn, bk)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = db_step(k, n_k, dmas)
    acc_ref[...] += int8_dot(xbuf[slot], wbuf[slot])

    @pl.when(k == n_k - 1)
    def _finish():
        y = act_fn(acc_ref[...].astype(jnp.float32) * meta_ref[0], act)
        o_ref[...] = maybe_kq(y, out_bits)


def fxp_matmul(x: jax.Array, w: jax.Array, *,
               xa_bits=(4, 10), w_bits=(2, 12), out_bits=(4, 10),
               act: str = "identity",
               bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool = False,
               datapath: str = "emulate",
               scale: Optional[jax.Array] = None,
               double_buffer: bool = False) -> jax.Array:
    """x: [M, K]; w: [K, N]. Returns f32 [M, N].

    emulate: x/w f32 or bf16, quantized in-kernel by (xa_bits, w_bits)
             (``None`` bits = passthrough).
    int8:    x/w int8 payloads; ``scale`` is the combined dequant scale
             s_x * s_w (traced f32 scalar or Python float).
    double_buffer: operands stream HBM -> 2-slot VMEM scratch via explicit
             prefetch DMAs (see module docstring); numerics identical.
    """
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)
    n_k = kdim // bk

    grid = (m // bm, n // bn, n_k)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars read in-body
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    out_shape = jax.ShapeDtypeStruct((m, n), jnp.float32)

    if datapath == "int8":
        assert x.dtype == jnp.int8 and w.dtype == jnp.int8, (x.dtype, w.dtype)
        assert scale is not None, "int8 datapath needs the combined scale"
        meta = jnp.asarray(scale, jnp.float32).reshape(1)
        if double_buffer:
            return pl.pallas_call(
                functools.partial(_kernel_db_int8, n_k=n_k, bm=bm, bn=bn,
                                  bk=bk, out_bits=out_bits, act=act),
                grid=grid,
                in_specs=[any_spec, any_spec, smem_spec],
                out_specs=o_spec,
                out_shape=out_shape,
                scratch_shapes=[pltpu.VMEM((2, bm, bk), jnp.int8),
                                pltpu.VMEM((2, bk, bn), jnp.int8),
                                pltpu.VMEM((bm, bn), jnp.int32),
                                pltpu.SemaphoreType.DMA((2, 2))],
                compiler_params=params,
                interpret=interpret,
                name="fxp_matmul",
            )(x, w, meta)
        return pl.pallas_call(
            functools.partial(_kernel_int8, n_k=n_k, out_bits=out_bits,
                              act=act),
            grid=grid,
            in_specs=[x_spec, w_spec, smem_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            compiler_params=params,
            interpret=interpret,
            name="fxp_matmul",
        )(x, w, meta)

    assert datapath == "emulate", datapath
    if double_buffer:
        return pl.pallas_call(
            functools.partial(_kernel_db, n_k=n_k, bm=bm, bn=bn, bk=bk,
                              xa_bits=xa_bits, w_bits=w_bits,
                              out_bits=out_bits, act=act),
            grid=grid,
            in_specs=[any_spec, any_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((2, bm, bk), x.dtype),
                            pltpu.VMEM((2, bk, bn), w.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
            compiler_params=params,
            interpret=interpret,
            name="fxp_matmul",
        )(x, w)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, xa_bits=xa_bits, w_bits=w_bits,
                          out_bits=out_bits, act=act),
        grid=grid,
        in_specs=[x_spec, w_spec],
        out_specs=o_spec,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
        name="fxp_matmul",
    )(x, w)
