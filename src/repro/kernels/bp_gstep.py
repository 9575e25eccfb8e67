"""bp_gstep: the TaxoNN G-chain step as one fused kernel.

    G_i = q_g( (G_{i+1} @ W_{i+1}^T) * f'(Z_i) )          (paper Eq. 8)

One VMEM-resident pass fuses the backward matmul, the activation-derivative
multiply (the paper's derivation unit), and the low-bit re-quantization of
the outgoing G — the intermediate (G @ W^T) never round-trips HBM.  This is
the TDM insight transplanted: the scarce resource on TPU is HBM bandwidth,
so the four TaxoNN multiplier time-slots become one fused VMEM pipeline.

Datapaths (see fxp_matmul.py): ``emulate`` computes the MAC at f32;
``int8`` takes G and W as int8 payloads, runs the MAC as int8 x int8 ->
int32 on the MXU with an exact int32 VMEM accumulator, and applies the
combined scale s_g * s_w once before the f' multiply.

Shapes: G [T, Dout], W [Din, Dout] (forward orientation), Z [T, Din]
(pre-activation of layer i; ``z=None`` with act="identity" skips the
derivative input entirely).  Output G_i [T, Din].
Grid (T/bm, Din/bn, Dout/bk); W^T is expressed through the BlockSpec index
map (no materialised transpose).

``double_buffer=True`` streams the G and W blocks HBM -> 2-slot VMEM via
explicit prefetch DMAs (grid step k waits the copy started at k-1 and
prefetches k+1 — see fxp_matmul's module docstring); Z keeps its implicit
blocked fetch (read once at the final k step).  Numerics identical.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import act_deriv, db_step, int8_dot, maybe_kq

# dot dims for G block [bm, bk] @ (W block [bn, bk])^T -> [bm, bn]
_GW_DIMS = (((1,), (1,)), ((), ()))


def _kernel(g_ref, w_ref, z_ref, o_ref, *, n_k: int, g_bits, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jax.lax.dot_general(g_ref[...], w_ref[...], _GW_DIMS,
                              preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _finish():
        y = o_ref[...]
        if z_ref is not None:
            y = y * act_deriv(z_ref[...].astype(jnp.float32), act)
        o_ref[...] = maybe_kq(y, g_bits)


def _kernel_int8(g_ref, w_ref, z_ref, meta_ref, o_ref, acc_ref, *,
                 n_k: int, g_bits, act: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(g_ref[...], w_ref[...], _GW_DIMS)

    @pl.when(k == n_k - 1)
    def _finish():
        y = acc_ref[...].astype(jnp.float32) * meta_ref[0]
        if z_ref is not None:
            y = y * act_deriv(z_ref[...].astype(jnp.float32), act)
        o_ref[...] = maybe_kq(y, g_bits)


def _db_dmas(g_hbm, w_hbm, gbuf, wbuf, sem, bm, bn, bk):
    i, j = pl.program_id(0), pl.program_id(1)

    def dma_g(slot, kk):
        return pltpu.make_async_copy(
            g_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
            gbuf.at[slot], sem.at[0, slot])

    def dma_w(slot, kk):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(j * bn, bn), pl.ds(kk * bk, bk)],
            wbuf.at[slot], sem.at[1, slot])

    return (dma_g, dma_w)


def _kernel_db(g_hbm, w_hbm, z_ref, o_ref, gbuf, wbuf, sem, *, n_k: int,
               bm: int, bn: int, bk: int, g_bits, act: str):
    k = pl.program_id(2)
    dmas = _db_dmas(g_hbm, w_hbm, gbuf, wbuf, sem, bm, bn, bk)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot = db_step(k, n_k, dmas)
    o_ref[...] += jax.lax.dot_general(gbuf[slot], wbuf[slot], _GW_DIMS,
                                      preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        y = o_ref[...]
        if z_ref is not None:
            y = y * act_deriv(z_ref[...].astype(jnp.float32), act)
        o_ref[...] = maybe_kq(y, g_bits)


def _kernel_db_int8(g_hbm, w_hbm, z_ref, meta_ref, o_ref, gbuf, wbuf,
                    acc_ref, sem, *, n_k: int, bm: int, bn: int, bk: int,
                    g_bits, act: str):
    k = pl.program_id(2)
    dmas = _db_dmas(g_hbm, w_hbm, gbuf, wbuf, sem, bm, bn, bk)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = db_step(k, n_k, dmas)
    acc_ref[...] += int8_dot(gbuf[slot], wbuf[slot], _GW_DIMS)

    @pl.when(k == n_k - 1)
    def _finish():
        y = acc_ref[...].astype(jnp.float32) * meta_ref[0]
        if z_ref is not None:
            y = y * act_deriv(z_ref[...].astype(jnp.float32), act)
        o_ref[...] = maybe_kq(y, g_bits)


def bp_gstep(g: jax.Array, w: jax.Array, z: Optional[jax.Array], *,
             g_bits=(2, 12), act: str = "relu",
             bm: int = 128, bn: int = 128, bk: int = 128,
             interpret: bool = False,
             datapath: str = "emulate",
             scale: Optional[jax.Array] = None,
             double_buffer: bool = False) -> jax.Array:
    """g: [T, Dout]; w: [Din, Dout]; z: [T, Din] or None. Returns [T, Din] f32.

    int8 datapath: g/w are int8 payloads, ``scale`` = s_g * s_w.
    double_buffer: explicit 2-slot DMA prefetch for the G/W blocks.
    """
    t, dout = g.shape
    din, dout2 = w.shape
    assert dout == dout2
    if z is None:
        assert act == "identity", act
    else:
        assert z.shape == (t, din)
    bm, bn, bk = min(bm, t), min(bn, din), min(bk, dout)
    assert t % bm == 0 and din % bn == 0 and dout % bk == 0
    n_k = dout // bk

    grid = (t // bm, din // bn, n_k)
    g_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))       # G
    w_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))       # W (T via dot dims)
    z_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))       # Z
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars read in-body
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    out_shape = jax.ShapeDtypeStruct((t, din), jnp.float32)

    if double_buffer:
        # slots keep the operands' own dtype so the MAC sees exactly what
        # the implicit-pipeline kernel sees (bf16 in -> bf16 MXU products)
        db_scratch = [pltpu.VMEM((2, bm, bk), g.dtype),
                      pltpu.VMEM((2, bn, bk), w.dtype)]
        db_sem = [pltpu.SemaphoreType.DMA((2, 2))]

    if datapath == "int8":
        assert g.dtype == jnp.int8 and w.dtype == jnp.int8, (g.dtype, w.dtype)
        assert scale is not None, "int8 datapath needs the combined scale"
        meta = jnp.asarray(scale, jnp.float32).reshape(1)
        if double_buffer:
            in_specs = [any_spec, any_spec]
            args = [g, w]
            if z is not None:
                in_specs.append(z_spec)
                args.append(z)
            in_specs.append(smem_spec)
            args.append(meta)

            def kern_db8(*refs):
                if z is not None:
                    g_r, w_r, z_r, m_r, o_r, gb, wb, a_r, sm = refs
                else:
                    g_r, w_r, m_r, o_r, gb, wb, a_r, sm = refs
                    z_r = None
                _kernel_db_int8(g_r, w_r, z_r, m_r, o_r, gb, wb, a_r, sm,
                                n_k=n_k, bm=bm, bn=bn, bk=bk, g_bits=g_bits,
                                act=act)

            return pl.pallas_call(
                kern_db8, grid=grid, in_specs=in_specs, out_specs=o_spec,
                out_shape=out_shape,
                scratch_shapes=db_scratch + [pltpu.VMEM((bm, bn), jnp.int32)]
                + db_sem,
                compiler_params=params, interpret=interpret,
                name="bp_gstep",
            )(*args)
        in_specs = [g_spec, w_spec]
        args = [g, w]
        if z is not None:
            in_specs.append(z_spec)
            args.append(z)
        in_specs.append(smem_spec)
        args.append(meta)

        def kern(*refs):
            if z is not None:
                g_r, w_r, z_r, m_r, o_r, a_r = refs
            else:
                g_r, w_r, m_r, o_r, a_r = refs
                z_r = None
            _kernel_int8(g_r, w_r, z_r, m_r, o_r, a_r, n_k=n_k,
                         g_bits=g_bits, act=act)

        return pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            compiler_params=params, interpret=interpret,
            name="bp_gstep",
        )(*args)

    assert datapath == "emulate", datapath
    if double_buffer:
        in_specs = [any_spec, any_spec]
        args = [g, w]
        if z is not None:
            in_specs.append(z_spec)
            args.append(z)

        def kern_db(*refs):
            if z is not None:
                g_r, w_r, z_r, o_r, gb, wb, sm = refs
            else:
                g_r, w_r, o_r, gb, wb, sm = refs
                z_r = None
            _kernel_db(g_r, w_r, z_r, o_r, gb, wb, sm, n_k=n_k, bm=bm,
                       bn=bn, bk=bk, g_bits=g_bits, act=act)

        return pl.pallas_call(
            kern_db, grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=out_shape, scratch_shapes=db_scratch + db_sem,
            compiler_params=params, interpret=interpret,
            name="bp_gstep",
        )(*args)
    in_specs = [g_spec, w_spec]
    args = [g, w]
    if z is not None:
        in_specs.append(z_spec)
        args.append(z)

    def kern(*refs):
        if z is not None:
            g_r, w_r, z_r, o_r = refs
        else:
            g_r, w_r, o_r = refs
            z_r = None
        _kernel(g_r, w_r, z_r, o_r, n_k=n_k, g_bits=g_bits, act=act)

    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
        out_shape=out_shape, compiler_params=params, interpret=interpret,
        name="bp_gstep",
    )(*args)
