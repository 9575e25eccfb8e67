"""Causal flash attention: the train step's and prefill's softmax attention
as Pallas kernels that never write the [B, H, T, T] scores to HBM.

softmax(Q K^T * scale + mask) V is computed blockwise with an online
softmax (``flash_attention``), and differentiated by two more kernels that
recompute each block's probabilities from the saved log-sum-exp:
``flash_attention_dq`` (dQ, one query block against its KV blocks) and
``flash_attention_dkv`` (dK and dV, one KV block against every query
block of every query head that reads it).

Precision is that of the materialised path it replaces
(``models.layers._sdpa_full``): Q/K/V in the dtype they arrive in, f32
scores (scaled in f32), softmax statistics and accumulators, and P (and dS
in the backward) cast to the operand dtype for the MXU.

Layout: the kernels read and write the model's own token-major layout,
[B, T, heads * dh] (a free reshape of [B, T, heads, hd]), one lane tile of
``dh * pack`` lanes per grid step, so no transpose runs around them.  A
head whose width divides 128 packs ``128 // hd`` heads into one tile (MHA
only): each head's scores come from Q with the other heads' lanes zeroed,
which leaves the contraction to that head's lanes, and its P @ V, dQ and
dK land in its own lanes by the same masking.  Any other head is padded
with zero columns to a multiple of 128 (zero columns of Q and K add
nothing to the scores; those of V give output columns that are sliced
off).  K and V keep their Hkv heads: query tile h reads KV tile
h // groups through its index map, so GQA needs no repeat.

Blocks: square, ``block_size(t)`` rows.  The causal mask (and a sliding
window, if any) decides for each block pair whether it is skipped (no
compute; its index map repeats the previous block, so no DMA either),
computed without a mask, or computed with the element mask.  The
log-sum-exp and the backward's row term di = rowsum(O * dO) travel as
lane-major rows [B, tiles, pack, T].

``flash_path`` is the gate ``models.layers.attention`` asks: the kernel
runs on a TPU (never in the interpreter) for causal attention over an
aligned T; the path taken is counted under "flash_attention" in
``kops.KERNEL_TRACES``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as kops

LANES = 128
NEG_INF = -1e30        # matches models.layers.NEG_INF
_NT = (((1,), (1,)), ((), ()))     # contract the last dims: a @ b^T
_F32 = jnp.float32


def head_layout(num_heads: int, num_kv_heads: int, head_dim: int):
    """(dh, pack): the lanes each head takes and the heads per lane tile."""
    if (LANES % head_dim == 0 and num_heads == num_kv_heads
            and num_heads % (LANES // head_dim) == 0):
        return head_dim, LANES // head_dim
    return -(-head_dim // LANES) * LANES, 1     # padded to whole tiles


def block_size(t: int) -> int:
    """Rows of a query or KV block: the largest of 512, 256, 128 that
    divides t."""
    for blk in (512, 256):
        if t % blk == 0:
            return blk
    return LANES


def flash_path(t: int, causal: bool) -> bool:
    """Whether attention over ``t`` tokens runs the kernel.  Counts the
    path under "flash_attention": "compiled", or the reason it was not
    taken."""
    if not causal:
        reason = "bidirectional"
    elif jax.sharding.get_abstract_mesh().size > 1:
        reason = "sharded"         # Mosaic calls are not partitioned
    elif t % LANES:
        reason = "unaligned"
    else:
        return not kops.interpret_mode("flash_attention")
    kops.note_path("flash_attention", reason)
    return False


def flash_attention(q, k, v, *, scale: float, window: Optional[int] = None,
                    interpret: bool = False):
    """Causal attention. q: [B, T, H, hd]; k, v: [B, T, Hkv, hd] with
    H % Hkv == 0 and T a multiple of 128.  Returns [B, T, H, hd]."""
    b, t, h, hd = q.shape
    dh, pack = head_layout(h, k.shape[2], hd)
    if window is not None and window >= t:
        window = None          # cuts no key at this length

    def lanes(x):
        if dh != hd:
            x = jnp.pad(x, ((0, 0),) * 3 + ((0, dh - hd),))
        return x.reshape(b, t, -1)

    o = _flash(lanes(q), lanes(k), lanes(v), float(scale), window, dh, pack,
               interpret)
    o = o.reshape(b, t, h, dh)
    return o if dh == hd else o[..., :hd]


# ---------------------------------------------------------------------------
# Block arithmetic (scalars on program ids; the window is static)
# ---------------------------------------------------------------------------

def _kv_range(i, blk: int, window: Optional[int]):
    """First and last KV block that query block i attends to."""
    if window is None:
        return 0, i
    return jnp.maximum(i * blk - window + 1, 0) // blk, i


def _q_range(j, blk: int, n: int, window: Optional[int]):
    """First and last query block that attends to KV block j."""
    if window is None:
        return j, n - 1
    return j, jnp.minimum(((j + 1) * blk + window - 2) // blk, n - 1)


def _needs_mask(qb, kb, blk: int, window: Optional[int]):
    """Whether some key of KV block kb is hidden from some query of query
    block qb (the pair itself is in range)."""
    partial = kb >= qb
    if window is not None:
        partial = partial | (kb * blk <= (qb + 1) * blk - 1 - window)
    return partial


def _visible(qb, kb, blk: int, window: Optional[int], transposed: bool):
    """Element mask of a [blk, blk] score block: rows are queries (or
    keys, ``transposed``)."""
    rows = lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    if transposed:
        rows, cols = cols, rows
    qpos, kpos = qb * blk + rows, kb * blk + cols
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return ok


def _when_in_range(run, masked, step):
    """Run ``step(masked=...)`` in the branch the block pair needs."""
    @pl.when(run & masked)
    def _():
        step(True)

    @pl.when(run & jnp.logical_not(masked))
    def _():
        step(False)


# ---------------------------------------------------------------------------
# Lane tiles: ``pack`` heads of ``dh`` lanes each
# ---------------------------------------------------------------------------

def _lane_head(width: int, dh: int):
    return lax.broadcasted_iota(jnp.int32, (1, width), 1) // dh


def _head(x, a: int, dh: int, pack: int):
    """x with every lane outside head a zeroed."""
    if pack == 1:
        return x
    return jnp.where(_lane_head(x.shape[-1], dh) == a, x, jnp.zeros_like(x))


def _per_head(cols, width: int, dh: int):
    """Per-head [blk, 128] columns (equal lanes) -> [blk, width], each
    head's lanes holding its own column."""
    out = _wide(cols[0], width)
    for a in range(1, len(cols)):
        out = jnp.where(_lane_head(width, dh) == a, cols[a], out)
    return out


def _wide(x, width: int):
    """[blk, 128] with equal lanes -> [blk, width]."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


def _column(row):
    """[1, blk] -> [blk, 128] with equal lanes."""
    return jnp.broadcast_to(row, (LANES, row.shape[-1])).T


def _scores(a, b, scale: Optional[float] = None):
    """a @ b^T in f32, times ``scale`` if given."""
    s = lax.dot_general(a, b, _NT, precision=lax.Precision.DEFAULT,
                        preferred_element_type=_F32)
    return s if scale is None else s * scale


def _mm(a, b):
    return jnp.dot(a, b, precision=lax.Precision.DEFAULT,
                   preferred_element_type=_F32)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, window, blk, n, dh, pack):
    i, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(i, blk, window)
    kb = lo + j
    width = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        ok = _visible(i, kb, blk, window, False) if masked else None
        alphas, pv = [], 0.0
        for a in range(pack):
            s = _scores(_head(q, a, dh, pack), k, scale)      # [bq, bk]
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_sc[a]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _wide(m_new, blk))
            if masked:   # a row with no visible key in this block adds nothing
                p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[a] = alpha * l_sc[a] + jnp.sum(p, axis=-1, keepdims=True)
            m_sc[a] = m_new
            alphas.append(alpha)
            pv = pv + _mm(p.astype(v.dtype), _head(v, a, dh, pack))
        acc_sc[...] = _per_head(alphas, width, dh) * acc_sc[...] + pv

    _when_in_range(kb <= hi, _needs_mask(i, kb, blk, window), step)

    @pl.when(j == n - 1)
    def _end():
        ls = [l_sc[a] for a in range(pack)]
        o_ref[...] = (acc_sc[...] / _per_head(ls, width, dh)).astype(
            o_ref.dtype)
        for a in range(pack):
            lse_ref[a:a + 1, :] = (m_sc[a] + jnp.log(ls[a])).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_sc, di_sc, acc_sc, *, scale, window, blk, n, dh, pack):
    i, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_range(i, blk, window)
    kb = lo + j

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        for a in range(pack):
            lse_sc[a] = _column(lse_ref[a:a + 1, :])
            di_sc[a] = _column(di_ref[a:a + 1, :])

    def step(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        ok = _visible(i, kb, blk, window, False) if masked else None
        for a in range(pack):
            s = _scores(_head(q, a, dh, pack), k, scale)
            if masked:     # exp(NEG_INF - lse) is exactly 0
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - _wide(lse_sc[a], blk))
            dp = _scores(_head(do, a, dh, pack), v)
            ds = p * (dp - _wide(di_sc[a], blk)) * scale
            acc_sc[...] += _mm(ds.astype(k.dtype), _head(k, a, dh, pack))

    _when_in_range(kb <= hi, _needs_mask(i, kb, blk, window), step)

    @pl.when(j == n - 1)
    def _end():
        dq_ref[...] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, window, blk, n, groups, dh, pack):
    # scores transposed, [keys, queries]: the row statistics broadcast
    # over sublanes and every product is a plain or a NT matmul
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    lo, hi = _q_range(j, blk, n, window)
    qb = lo + i

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        ok = _visible(qb, j, blk, window, True) if masked else None
        for a in range(pack):
            qa, doa = _head(q, a, dh, pack), _head(do, a, dh, pack)
            st = _scores(k, qa, scale)                         # [bk, bq]
            if masked:
                st = jnp.where(ok, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[a:a + 1, :])
            dv_sc[...] += _mm(pt.astype(do.dtype), doa)
            dst = pt * (_scores(v, doa) - di_ref[a:a + 1, :]) * scale
            dk_sc[...] += _mm(dst.astype(q.dtype), qa)

    _when_in_range(qb <= hi, _needs_mask(qb, j, blk, window), step)

    @pl.when((r == groups - 1) & (i == n - 1))
    def _end():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Calls: q, k, v, o are [B, T, tiles * width]; rows are [B, tiles, pack, T]
# ---------------------------------------------------------------------------

def _shape(q, k, dh, pack):
    b, t, w = q.shape
    width = dh * pack
    tiles = w // width
    blk = block_size(t)
    return b, t, width, tiles, w // k.shape[2], blk, t // blk


def _cost(q, k, dh, matmuls: int, passes: int):
    """Causal half of ``matmuls`` [T, T, dh] products per head, and the
    bytes of ``passes`` reads or writes of a Q-sized array."""
    b, t, w = q.shape
    pairs = b * (w // dh) * t * (t + 1) // 2
    return pl.CostEstimate(flops=2 * matmuls * pairs * dh,
                           transcendentals=pairs,
                           bytes_accessed=passes * q.size * q.dtype.itemsize
                           + 2 * k.size * k.dtype.itemsize)


def _params(parallel: int, arbitrary: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * parallel
        + ("arbitrary",) * arbitrary)


def _forward(q, k, v, scale, window, dh, pack, interpret):
    b, t, width, tiles, groups, blk, n = _shape(q, k, dh, pack)

    def q_map(bi, hi, i, j):
        return bi, i, hi

    def kv_map(bi, hi, i, j):
        lo, last = _kv_range(i, blk, window)
        return bi, jnp.minimum(lo + j, last), hi // groups

    def row_map(bi, hi, i, j):
        return bi, hi, 0, i

    tile = pl.BlockSpec((None, blk, width), q_map)
    kv = pl.BlockSpec((None, blk, width), kv_map)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, window=window, blk=blk,
                          n=n, dh=dh, pack=pack),
        grid=(b, tiles, n, n),
        in_specs=[tile, kv, kv],
        out_specs=[tile, pl.BlockSpec((None, None, pack, blk), row_map)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, tiles, pack, t), _F32)],
        scratch_shapes=[pltpu.VMEM((pack, blk, LANES), _F32),
                        pltpu.VMEM((pack, blk, LANES), _F32),
                        pltpu.VMEM((blk, width), _F32)],
        compiler_params=_params(3, 1),
        cost_estimate=_cost(q, k, dh, 2, 2),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def _backward_dq(q, k, v, do, lse, di, scale, window, dh, pack, interpret):
    b, t, width, tiles, groups, blk, n = _shape(q, k, dh, pack)

    def q_map(bi, hi, i, j):
        return bi, i, hi

    def kv_map(bi, hi, i, j):
        lo, last = _kv_range(i, blk, window)
        return bi, jnp.minimum(lo + j, last), hi // groups

    def row_map(bi, hi, i, j):
        return bi, hi, 0, i

    tile = pl.BlockSpec((None, blk, width), q_map)
    kv = pl.BlockSpec((None, blk, width), kv_map)
    row = pl.BlockSpec((None, None, pack, blk), row_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, window=window, blk=blk,
                          n=n, dh=dh, pack=pack),
        grid=(b, tiles, n, n),
        in_specs=[tile, kv, kv, tile, row, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((pack, blk, LANES), _F32),
                        pltpu.VMEM((pack, blk, LANES), _F32),
                        pltpu.VMEM((blk, width), _F32)],
        compiler_params=_params(3, 1),
        cost_estimate=_cost(q, k, dh, 3, 3),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)


def _backward_dkv(q, k, v, do, lse, di, scale, window, dh, pack, interpret):
    b, t, width, tiles, groups, blk, n = _shape(q, k, dh, pack)

    def q_block(j, i):
        lo, last = _q_range(j, blk, n, window)
        return jnp.minimum(lo + i, last)

    def q_map(bi, hk, j, r, i):
        return bi, q_block(j, i), hk * groups + r

    def row_map(bi, hk, j, r, i):
        return bi, hk * groups + r, 0, q_block(j, i)

    def kv_map(bi, hk, j, r, i):
        return bi, j, hk

    tile = pl.BlockSpec((None, blk, width), q_map)
    kv = pl.BlockSpec((None, blk, width), kv_map)
    row = pl.BlockSpec((None, None, pack, blk), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, window=window, blk=blk,
                          n=n, groups=groups, dh=dh, pack=pack),
        grid=(b, tiles // groups, n, groups, n),
        in_specs=[tile, kv, kv, tile, row, row],
        out_specs=[kv, kv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, width), _F32),
                        pltpu.VMEM((blk, width), _F32)],
        compiler_params=_params(3, 2),
        cost_estimate=_cost(q, k, dh, 4, 2),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, window, dh, pack, interpret):
    with jax.named_scope("attention"):
        return _forward(q, k, v, scale, window, dh, pack, interpret)[0]


def _flash_fwd(q, k, v, scale, window, dh, pack, interpret):
    # the scope keeps the kernel's own name innermost under a transform:
    # jvp(attention)/flash_attention, not jvp(flash_attention)
    with jax.named_scope("attention"):
        o, lse = _forward(q, k, v, scale, window, dh, pack, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, window, dh, pack, interpret, res, do):
    q, k, v, o, lse = res
    with jax.named_scope("attention"):
        b, t, _ = q.shape
        di = jnp.sum((o.astype(_F32) * do.astype(_F32)).reshape(
            b, t, lse.shape[1], pack, dh), axis=-1).transpose(0, 2, 3, 1)
        dq = _backward_dq(q, k, v, do, lse, di, scale, window, dh, pack,
                          interpret)
        dk, dv = _backward_dkv(q, k, v, do, lse, di, scale, window, dh, pack,
                               interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)
