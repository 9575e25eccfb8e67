"""sgd_dw_update: fused dW computation + in-place SGD step.

    W <- q_w( W - lr * (X^T @ G) )           (paper Eq. 9 + Eq. 1, step 4)

The gradient tensor dW = X^T G is accumulated in VMEM across the token
blocks and folded into the weight update in the same kernel — dW never
exists in HBM.  This is the TaxoNN fused-update property (gradient
lifetime = one PE pass) expressed at the memory-hierarchy level that
matters on TPU.

Datapaths: ``emulate`` accumulates the outer product at f32; ``int8`` takes
X and G as int8 payloads (the activation and gradient storage formats), runs
the MAC as int8 x int8 -> int32 with an exact int32 VMEM accumulator, and
rescales by s_x * s_g once at the final step, where the master-weight f32
update happens.

``w=None`` turns the kernel into its dW-only form (returns X^T @ G, no
update) — the shape emitted to ``custom_vjp`` backward rules and the int8
tile source for the compressed dW all-reduce.

Shapes: X [T, Din], G [T, Dout], W [Din, Dout] -> [Din, Dout].
Grid (Din/bm, Dout/bn, T/bk): the contraction is over tokens.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import int8_dot, maybe_kq

# (X block [bk, bm])^T @ G block [bk, bn] -> [bm, bn]
_XG_DIMS = (((0,), (0,)), ((), ()))


def _kernel(x_ref, g_ref, w_ref, lr_ref, o_ref, *, n_k: int, w_bits):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jax.lax.dot_general(x_ref[...], g_ref[...], _XG_DIMS,
                              preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _finish():
        if w_ref is None:
            o_ref[...] = maybe_kq(o_ref[...], w_bits)
        else:
            w_new = w_ref[...].astype(jnp.float32) - lr_ref[0] * o_ref[...]
            o_ref[...] = maybe_kq(w_new, w_bits)


def _kernel_int8(x_ref, g_ref, w_ref, meta_ref, o_ref, acc_ref, *,
                 n_k: int, w_bits):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += int8_dot(x_ref[...], g_ref[...], _XG_DIMS)

    @pl.when(k == n_k - 1)
    def _finish():
        dw = acc_ref[...].astype(jnp.float32) * meta_ref[0]  # s_x * s_g
        if w_ref is None:
            o_ref[...] = maybe_kq(dw, w_bits)
        else:
            w_new = w_ref[...].astype(jnp.float32) - meta_ref[1] * dw
            o_ref[...] = maybe_kq(w_new, w_bits)


def sgd_dw_update(x: jax.Array, g: jax.Array, w: Optional[jax.Array], lr,
                  *, w_bits=None,
                  bm: int = 128, bn: int = 128, bk: int = 128,
                  interpret: bool = False,
                  datapath: str = "emulate",
                  scale: Optional[jax.Array] = None) -> jax.Array:
    """x: [T, Din]; g: [T, Dout]; w: [Din, Dout] or None; lr scalar.

    Returns W - lr * x^T g (optionally re-quantized to (I,F)), or the raw
    dW = x^T g when ``w is None``.  int8 datapath: x/g are int8 payloads,
    ``scale`` = s_x * s_g.
    """
    t, din = x.shape
    t2, dout = g.shape
    assert t == t2
    if w is not None:
        assert w.shape == (din, dout)
    bm, bn, bk = min(bm, din), min(bn, dout), min(bk, t)
    assert din % bm == 0 and dout % bn == 0 and t % bk == 0
    n_k = t // bk

    grid = (din // bm, dout // bn, n_k)
    x_spec = pl.BlockSpec((bk, bm), lambda i, j, k: (k, i))   # X
    g_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))   # G
    w_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))   # W
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars read in-body
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    out_shape = jax.ShapeDtypeStruct((din, dout), jnp.float32)

    if datapath == "int8":
        assert x.dtype == jnp.int8 and g.dtype == jnp.int8, (x.dtype, g.dtype)
        assert scale is not None, "int8 datapath needs the combined scale"
        meta = jnp.stack([jnp.asarray(scale, jnp.float32),
                          jnp.asarray(lr, jnp.float32)])
        in_specs = [x_spec, g_spec]
        args = [x, g]
        if w is not None:
            in_specs.append(w_spec)
            args.append(w)
        in_specs.append(smem_spec)
        args.append(meta)

        def kern(*refs):
            if w is not None:
                x_r, g_r, w_r, m_r, o_r, a_r = refs
            else:
                x_r, g_r, m_r, o_r, a_r = refs
                w_r = None
            _kernel_int8(x_r, g_r, w_r, m_r, o_r, a_r, n_k=n_k, w_bits=w_bits)

        return pl.pallas_call(
            kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            compiler_params=params, interpret=interpret,
            name="sgd_dw_update",
        )(*args)

    assert datapath == "emulate", datapath
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    in_specs = [x_spec, g_spec]
    args = [x, g]
    if w is not None:
        in_specs.append(w_spec)
        args.append(w)
    in_specs.append(smem_spec)
    args.append(lr_arr)

    def kern(*refs):
        if w is not None:
            x_r, g_r, w_r, lr_r, o_r = refs
        else:
            x_r, g_r, lr_r, o_r = refs
            w_r = None
        _kernel(x_r, g_r, w_r, lr_r, o_r, n_k=n_k, w_bits=w_bits)

    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=o_spec,
        out_shape=out_shape, compiler_params=params, interpret=interpret,
        name="sgd_dw_update",
    )(*args)
