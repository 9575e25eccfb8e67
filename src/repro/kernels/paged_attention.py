"""Fused paged-attention decode kernel: block-table gather + int8 KV
dequant + flash-style softmax in one ``pallas_call``.

One grid step per decode slot.  The block table and per-slot lengths ride
scalar prefetch (``PrefetchScalarGridSpec``) so the kernel can index the
pool before the body runs; the gather loop pulls each of the slot's blocks
out of the VMEM-resident pool with a dynamic slice, dequantizes int8
payloads against their per-token scales on the way, and lands them in a
contiguous [T, kv_heads, head_dim] scratch.  The softmax is single-tile
flash: one max-subtracted exponentiation + normalization over the whole
gathered row (the row fits VMEM by construction — ``ops.tune_paged``
budgets it), computed with the exact op sequence of the jnp reference, so
kernel and ref are BITWISE identical in interpret mode (tested in
tests/test_paging.py).

``paged_attention`` picks kernel vs ref: the kernel when the
``tune_paged`` budget admits the pool, the jnp gather path otherwise.
Shapes the budget rejects are exactly the ones whose pool belongs in HBM —
the multi-pass DMA variant is the TPU-scale follow-up; the ref path keeps
semantics identical meanwhile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kops

NEG_INF = -1e30  # matches models.layers.NEG_INF


def _expand_heads(k, groups: int):
    """[T, Hkv, hd] -> [T, Hkv*groups, hd] (GQA repeat, layers._expand_kv
    order)."""
    if groups == 1:
        return k
    t, hkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, None, :], (t, hkv, groups, hd))
    return k.reshape(t, hkv * groups, hd)


def _attend(q, kk, vv, length, t, scale):
    """The shared softmax tail: q [1,H,hd]; kk/vv [T,H,hd] (expanded).

    Op-for-op the batched math of ``serving.engine._paged_attention`` with
    B=1, C=1 — the bitwise contract between kernel and ref lives here.
    The size-1 batch axis is left out of the contractions: Mosaic's
    matmul takes one batch dim (the heads).
    """
    s = jnp.einsum("qhd,khd->hqk", q, kk,
                   preferred_element_type=jnp.float32) * scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
    ok = kpos <= length
    s = s + jnp.where(ok, 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("hqk,khd->qhd", p, vv,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out[0]  # [H, hd]


def _kernel(tbl_ref, len_ref, q_ref, kp_ref, vp_ref, o_ref, kg_ref, vg_ref,
            *, m: int, bs: int, groups: int, scale: float):
    b = pl.program_id(0)
    dt = q_ref.dtype
    for i in range(m):  # static trip count: max blocks per sequence
        bid = tbl_ref[b, i]
        kb = kp_ref[pl.ds(bid, 1)][0]
        vb = vp_ref[pl.ds(bid, 1)][0]
        kg_ref[pl.ds(i * bs, bs)] = kb.astype(dt)
        vg_ref[pl.ds(i * bs, bs)] = vb.astype(dt)
    kk = _expand_heads(kg_ref[...], groups)
    vv = _expand_heads(vg_ref[...], groups)
    o_ref[...] = _attend(q_ref[...][0][None], kk, vv, len_ref[b],
                         m * bs, scale)[None]


def _kernel_int8(tbl_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                 o_ref, kg_ref, vg_ref, *, m: int, bs: int, groups: int,
                 scale: float):
    b = pl.program_id(0)
    dt = q_ref.dtype
    for i in range(m):
        bid = tbl_ref[b, i]
        kb = kp_ref[pl.ds(bid, 1)][0]
        vb = vp_ref[pl.ds(bid, 1)][0]
        # [block, 1, 1] -> [block, 1, hd]: Mosaic broadcasts lanes and
        # sublanes in separate steps
        lanes = (bs, 1, kb.shape[-1])
        ks = jnp.broadcast_to(ks_ref[pl.ds(bid, 1)][0], lanes)
        vs = jnp.broadcast_to(vs_ref[pl.ds(bid, 1)][0], lanes)
        kg_ref[pl.ds(i * bs, bs)] = kb.astype(dt) * ks.astype(dt)
        vg_ref[pl.ds(i * bs, bs)] = vb.astype(dt) * vs.astype(dt)
    kk = _expand_heads(kg_ref[...], groups)
    vv = _expand_heads(vg_ref[...], groups)
    o_ref[...] = _attend(q_ref[...][0][None], kk, vv, len_ref[b],
                         m * bs, scale)[None]


@functools.partial(jax.jit, static_argnames=("groups", "scale"))
def _ref(q, pool_l, tables, lens, groups: int, scale: float):
    """jnp gather fallback — the same math the engine's ref branch runs."""
    dt = q.dtype
    kk = pool_l["k"][tables]
    vv = pool_l["v"][tables]
    b, m, bs, hkv, hd = kk.shape
    kk = kk.reshape(b, m * bs, hkv, hd)
    vv = vv.reshape(b, m * bs, hkv, hd)
    if "k_scale" in pool_l:
        ks = pool_l["k_scale"][tables].reshape(b, m * bs)
        vs = pool_l["v_scale"][tables].reshape(b, m * bs)
        kk = kk.astype(dt) * ks[..., None, None].astype(dt)
        vv = vv.astype(dt) * vs[..., None, None].astype(dt)
    else:
        kk = kk.astype(dt)
        vv = vv.astype(dt)
    kk = jax.vmap(_expand_heads, in_axes=(0, None))(kk, groups)
    vv = jax.vmap(_expand_heads, in_axes=(0, None))(vv, groups)
    s = jnp.einsum("bqhd,bkhd->bhqk", q[:, None], kk,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(m * bs)
    ok = kpos[None, None, :] <= lens[:, None, None]
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None]
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv,
                     preferred_element_type=jnp.float32).astype(dt)
    return out[:, 0]


def _call_kernel(q, pool_l, tables, lens, groups: int, scale: float):
    b, h, hd = q.shape
    m = tables.shape[1]
    n, bs, hkv, _ = pool_l["k"].shape
    int8 = "k_scale" in pool_l
    t = m * bs
    interpret = kops.interpret_mode("paged_attention")

    def full(x):
        nd = x.ndim
        return pl.BlockSpec(x.shape, lambda i, *_, _nd=nd: (0,) * _nd)

    in_specs = [pl.BlockSpec((1, h, hd), lambda i, *_: (i, 0, 0)),
                full(pool_l["k"]), full(pool_l["v"])]
    args = [q, pool_l["k"], pool_l["v"]]
    if int8:
        body = functools.partial(_kernel_int8, m=m, bs=bs, groups=groups,
                                 scale=scale)
        # per-token scales as [N, block, 1, 1]: a block's [block, 1, 1]
        # slice broadcasts over (heads, hd) without an in-kernel reshape
        scales = [pool_l[k].reshape(n, bs, 1, 1)
                  for k in ("k_scale", "v_scale")]
        in_specs += [full(x) for x in scales]
        args += scales
    else:
        body = functools.partial(_kernel, m=m, bs=bs, groups=groups,
                                 scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((t, hkv, hd), q.dtype),
                        pltpu.VMEM((t, hkv, hd), q.dtype)],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), *args)


def paged_attention(q, pool_l: dict, tables, lens, *, groups: int,
                    scale: float):
    """Paged-attention decode for one layer.

    q: [B, H, hd] (post-rope query for the incoming token); pool_l: one
    layer's pool leaves ({"k","v"[,"k_scale","v_scale"]}); tables: [B, M]
    int32 block tables; lens: [B] int32 — the incoming token's position
    (kpos <= lens[b] attends).  Returns [B, H, hd].
    """
    n, bs, hkv, hd = pool_l["k"].shape
    m = tables.shape[1]
    fits = kops.tune_paged(n, bs, m, hkv, hd, groups,
                           itemsize=pool_l["k"].dtype.itemsize)
    if fits is None:
        kops.note_path("paged_attention", "over_vmem")
        return _ref(q, pool_l, tables, lens, groups, scale)
    return _call_kernel(q, pool_l, tables, lens, groups, scale)
