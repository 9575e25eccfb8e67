"""Functional layer library: norms, RoPE, attention variants, MLP, MoE.

Pure functions over explicit parameter pytrees (no flax).  Every ``apply``
comes with a matching ``init``.  Layers support three execution modes:

  * full-sequence (training / prefill, causal or bidirectional mask); on
    a TPU, causal attention over an aligned T runs the Pallas flash kernels
    of ``kernels.flash_attention`` (scores never leave VMEM)
  * chunked online-softmax attention for long sequences (flash-style, pure
    JAX ``lax.scan`` over KV blocks — bounded memory at 32k+)
  * single-token decode against a KV cache (GQA ring-buffer for SWA, MLA
    absorbed-matmul over the compressed c_kv cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.dist.api import constrain, model_axis_size_ctx, perf_opt
from repro.kernels import flash_attention as FA
from repro.kernels import ops as kops
from repro.kernels.common import act_deriv as _act_deriv, act_fn as _act_fn
from repro.models.config import ModelConfig
from repro.util.scan import xscan

Array = jax.Array

ATTN_CHUNK_THRESHOLD = 8192   # use online-softmax scan above this seq len
ATTN_KV_BLOCK = 1024

NEG_INF = -1e30  # additive mask value (finite: avoids NaN in masked softmax rows)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dtype)


def apply_norm(params, x: Array, cfg: ModelConfig) -> Array:
    if cfg.norm_kind == "layernorm":
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


def init_norm(d: int, cfg: ModelConfig):
    return init_layernorm(d) if cfg.norm_kind == "layernorm" else init_rmsnorm(d)


# ---------------------------------------------------------------------------
# RoPE (half-rotation convention)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [..., T, H, hd]; positions: broadcastable to [..., T]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]                 # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The kernel-datapath dense unit (TaxoNN PE array as a custom_vjp op)
# ---------------------------------------------------------------------------
#
# ``dense_unit(x, w, act)`` computes act(x @ w) through the Pallas kernel
# datapath selected by the ambient KernelBackend (see repro.kernels.ops):
# forward is ``fxp_matmul``; backward emits ``bp_gstep`` (dx, Eq. 8's matmul
# leg) and the dW-only form of ``sgd_dw_update`` (Eq. 9).  On the "int8"
# backend the operands move as int8 payloads with traced absmax scales and
# the MACs run int8 x int8 -> int32 — the paper's reuse of the inference
# low-bit PEs for the training passes.  The engine's STE wrappers own the
# (I,F) grid *around* this op, so the unit itself stays format-agnostic and
# one compiled step still serves every bit schedule.
#
# With the backend "off" (the CPU default) callers skip this path entirely
# and keep the original jnp einsums — bit-identical to the pre-kernel code.

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dense_unit(x, w, act, backend):
    y, _ = _dense_unit_fwd(x, w, act, backend)
    return y


def _dense_unit_fwd(x, w, act, backend):
    with jax.named_scope("dense_unit"):
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        z = kops.dense_fwd(x2, w, backend)              # f32 [M, N]
        y = _act_fn(z, act).astype(x.dtype).reshape(
            shape[:-1] + (w.shape[1],))
    # z is a per-layer residual: under the engine's remat-per-layer backward
    # it lives only for one scan step (the paper's derivation-unit register)
    return y, (x2, w, z if act != "identity" else None, shape)


def _dense_unit_bwd(act, backend, res, dy):
    with jax.named_scope("dense_unit"):
        x2, w, z, shape = res
        dy2 = dy.reshape(-1, dy.shape[-1]).astype(jnp.float32)
        dz = dy2 if z is None else dy2 * _act_deriv(z, act)
        dx = kops.dense_bwd_dx(dz, w, backend)           # Eq. 8 matmul leg
        dw = kops.dense_bwd_dw(x2, dz, backend)          # Eq. 9 outer product
        return dx.reshape(shape).astype(x2.dtype), dw.astype(w.dtype)


_dense_unit.defvjp(_dense_unit_fwd, _dense_unit_bwd)


def dense_unit(x, w, act: str = "identity",
               backend: Optional[str] = None) -> Array:
    """act(x @ w) on the active kernel datapath. x: [..., K]; w: [K, N]."""
    backend = backend or kops.current_backend()
    if backend == "off":
        with jax.named_scope("dense_unit"):
            return _act_fn((x @ w.astype(x.dtype)).astype(jnp.float32),
                           act).astype(x.dtype)
    return _dense_unit(x, w, act, backend)


def _proj3(x: Array, w3: Array, backend: str) -> Array:
    """Projection einsum "btd,dhk->bthk" through the dense unit."""
    d, h, hd = w3.shape
    y = _dense_unit(x, w3.reshape(d, h * hd), "identity", backend)
    return y.reshape(x.shape[:-1] + (h, hd))


# ---------------------------------------------------------------------------
# Dense attention (GQA / MQA / SWA)
# ---------------------------------------------------------------------------

def alloc_heads(cfg: ModelConfig) -> int:
    return cfg.padded_heads or cfg.num_heads


def _live_head_mask(cfg: ModelConfig, dtype) -> Optional[Array]:
    """[H_alloc] mask, 1 for real heads.  Heads are grouped per KV head
    (layout [Hkv, group]); padding extends each group, so the original
    query->KV mapping is preserved.  Masking wo rows keeps dead heads at
    exactly zero output AND zero gradient -> function == unpadded model."""
    hp, h, hkv = alloc_heads(cfg), cfg.num_heads, cfg.num_kv_heads
    if hp == h:
        return None
    g, gp = h // hkv, hp // hkv
    mask = (jnp.arange(gp) < g).astype(dtype)
    return jnp.broadcast_to(mask, (hkv, gp)).reshape(hp)


def init_attention(key, cfg: ModelConfig):
    D, Hkv, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    H = alloc_heads(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D ** -0.5
    p = {
        "wq": jax.random.normal(k1, (D, H, hd), jnp.float32) * s,
        "wk": jax.random.normal(k2, (D, Hkv, hd), jnp.float32) * s,
        "wv": jax.random.normal(k3, (D, Hkv, hd), jnp.float32) * s,
        "wo": jax.random.normal(k4, (H, hd, D), jnp.float32) * (H * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), jnp.float32)
        p["bk"] = jnp.zeros((Hkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((Hkv, hd), jnp.float32)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    dt = x.dtype
    backend = kops.current_backend()
    if backend != "off":
        # §Kernels: QKV projections on the TaxoNN kernel datapath
        q = _proj3(x, params["wq"], backend)
        k = _proj3(x, params["wk"], backend)
        v = _proj3(x, params["wv"], backend)
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
        k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(dt))
        v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k: Array, groups: int) -> Array:
    """[B, T, Hkv, hd] -> [B, T, Hkv*groups, hd] by repeat (GQA)."""
    if groups == 1:
        return k
    b, t, hkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, t, hkv, groups, hd))
    return k.reshape(b, t, hkv * groups, hd)


def _attn_mask(t_q: int, t_kv: int, causal: bool, window: Optional[int],
               q_offset: int = 0) -> Array:
    """Additive mask [t_q, t_kv]; query i maps to absolute position i+q_offset."""
    qpos = jnp.arange(t_q)[:, None] + q_offset
    kpos = jnp.arange(t_kv)[None, :]
    ok = jnp.ones((t_q, t_kv), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa_full(q, k, v, mask, scale) -> Array:
    """Standard softmax attention, scores materialised. q,k,v: [B,T,H,hd]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + mask[None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sdpa_chunked(q, k, v, causal, window, scale) -> Array:
    """Online-softmax attention, scanning KV blocks (flash-style, pure JAX).

    Memory is O(T * KV_BLOCK) instead of O(T^2).  Used for 32k+ sequences.
    K and V head dims may differ (MLA: qk 192 vs v 128).
    """
    b, t, h, hd = q.shape
    dv = v.shape[-1]
    blk = min(ATTN_KV_BLOCK, t)
    nblk = (t + blk - 1) // blk
    pad = nblk * blk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, blk, h, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, blk, h, dv).transpose(1, 0, 2, 3, 4)

    qpos = jnp.arange(t)[:, None]

    def body(carry, xs):
        acc, m, lse = carry  # acc [b,t,h,hd] f32, m/lse [b,h,t] f32
        kblk, vblk, blk_idx = xs
        kpos = blk_idx * blk + jnp.arange(blk)[None, :]
        ok = jnp.ones((t, blk), bool)
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        ok &= (kpos < t)  # padding
        mask = jnp.where(ok, 0.0, NEG_INF)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        s = s + mask[None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = lse * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, t, h, dv), jnp.float32)
    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    (acc, m, lse), _ = xscan(
        body, (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(lse, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _masked_wo(params, cfg: ModelConfig, dt):
    wo = params["wo"].astype(dt)
    mask = _live_head_mask(cfg, dt)
    if mask is not None:
        wo = wo * mask[:, None, None]
    return wo


def attention(params, x: Array, cfg: ModelConfig, positions: Array,
              causal: bool = True, return_kv: bool = False):
    """Full-sequence attention (training / prefill). x: [B, T, D].

    ``return_kv=True`` additionally returns the (pre-GQA-expansion) rotated
    K/V so prefill can seed the decode cache without recomputation.
    """
    dt = x.dtype
    b, t, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    groups = q.shape[2] // cfg.num_kv_heads
    scale = cfg.head_dim ** -0.5
    with jax.named_scope("attention"):
        kx = _expand_kv(k, groups)
        vx = _expand_kv(v, groups)
        # §Perf "flash_attn": online-softmax at every length (never
        # materialise the [B,H,T,T] score tensor); default only above the
        # chunk threshold
        if t > ATTN_CHUNK_THRESHOLD or (perf_opt("flash_attn") and t > 1024):
            out = _sdpa_chunked(q, kx, vx, causal, cfg.swa_window, scale)
        elif FA.flash_path(t, causal):
            # the Pallas flash kernels (TPU): scores stay in VMEM, and the
            # KV heads are read per group, not repeated
            out = FA.flash_attention(q, k, v, scale=scale,
                                     window=cfg.swa_window)
        else:
            mask = _attn_mask(t, t, causal, cfg.swa_window)
            out = _sdpa_full(q, kx, vx, mask, scale)
    wo = _masked_wo(params, cfg, dt)
    backend = kops.current_backend()
    if backend != "off":
        # §Kernels: output projection on the TaxoNN kernel datapath
        h_, hd_, d_ = wo.shape
        y = _dense_unit(out.reshape(b, t, h_ * hd_),
                        wo.reshape(h_ * hd_, d_), "identity", backend)
    else:
        y = jnp.einsum("bthk,hkd->btd", out, wo)
    if return_kv:
        return y, (k, v)
    return y


def fill_ring(k: Array, length: int) -> Array:
    """Place a [B,T,...] sequence into a ring buffer of ``length`` slots so
    that token at absolute position p sits at slot p % length (matching
    ``attention_decode``'s indexing).  Keeps the last ``length`` tokens."""
    t = k.shape[1]
    if t <= length:
        pad = [(0, 0)] * k.ndim
        pad[1] = (0, length - t)
        return jnp.pad(k, pad)
    tail = k[:, t - length:]
    idx = (jnp.arange(length) - t) % length
    return jnp.take(tail, idx, axis=1)


# --- decode with KV cache -------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Ring-buffer KV cache.  For SWA archs the buffer is min(window, max_len)
    long (a serving memory win the sliding window makes free)."""
    length = max_len if cfg.swa_window is None else min(cfg.swa_window, max_len)
    shape = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def attention_decode(params, x: Array, cfg: ModelConfig, cache: dict,
                     pos: Array) -> tuple[Array, dict]:
    """One-token decode. x: [B, 1, D]; pos: scalar int32 (current position)."""
    b = x.shape[0]
    q, k, v = _project_qkv(params, x, cfg, jnp.full((b, 1), pos))
    return attention_decode_tail(params, q, k, v, x.dtype, cfg, cache, pos)


def attention_decode_tail(params, q: Array, k: Array, v: Array, dt,
                          cfg: ModelConfig, cache: dict, pos: Array
                          ) -> tuple[Array, dict]:
    """Cache write + ring-masked softmax + output projection — everything
    after the prologue, shared by the unfused path above and the fused
    decode-prologue kernel (kernels.decode_prologue) so both prologues feed
    bit-identical attention math."""
    length = cache["k"].shape[1]
    slot = jnp.mod(pos, length)  # ring buffer when SWA; plain index otherwise
    ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                  (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                  (0, slot, 0, 0))
    groups = q.shape[2] // cfg.num_kv_heads
    kk = _expand_kv(ck.astype(dt), groups)
    vv = _expand_kv(cv.astype(dt), groups)
    scale = cfg.head_dim ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                   preferred_element_type=jnp.float32) * scale
    # valid slots: absolute kpos <= pos and kpos > pos - length (ring validity)
    idx = jnp.arange(length)
    # absolute position stored in slot i (ring): the latest write to slot i
    # occurred at abs = pos - ((slot - i) mod length)
    abs_pos = pos - jnp.mod(slot - idx, length)
    ok = (abs_pos >= 0) & (abs_pos <= pos)
    if cfg.swa_window is not None:
        ok &= abs_pos > pos - cfg.swa_window
    s = s + jnp.where(ok, 0.0, NEG_INF)[None, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    y = jnp.einsum("bthk,hkd->btd", out, _masked_wo(params, cfg, dt))
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    D, H = cfg.d_model, cfg.num_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    s = D ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (D, H, dn + dr), jnp.float32) * s,
        "w_dkv": jax.random.normal(ks[1], (D, r), jnp.float32) * s,
        "w_kpe": jax.random.normal(ks[2], (D, dr), jnp.float32) * s,
        "w_uk": jax.random.normal(ks[3], (r, H, dn), jnp.float32) * r ** -0.5,
        "w_uv": jax.random.normal(ks[4], (r, H, dv), jnp.float32) * r ** -0.5,
        "wo": jax.random.normal(ks[5], (H, dv, D), jnp.float32) * (H * dv) ** -0.5,
        "ckv_norm": init_rmsnorm(r),
    }


def mla_attention(params, x: Array, cfg: ModelConfig, positions: Array,
                  return_cache: bool = False):
    """Full-sequence MLA (training / prefill): materialise per-head K/V."""
    dt = x.dtype
    b, t, _ = x.shape
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    c_kv = rmsnorm(params["ckv_norm"],
                   jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(dt)),
                   cfg.norm_eps)
    k_pe = apply_rope(
        jnp.einsum("btd,dr->btr", x, params["w_kpe"].astype(dt))[:, :, None, :],
        positions, cfg.rope_theta)                         # [B,T,1,dr]
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uk"].astype(dt))
    v = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uv"].astype(dt))

    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, (b, t, cfg.num_heads, dr))], axis=-1)
    qq = jnp.concatenate([q_nope, q_pe], axis=-1)
    scale = (dn + dr) ** -0.5
    with jax.named_scope("attention"):
        if t > ATTN_CHUNK_THRESHOLD:
            out = _sdpa_chunked(qq, k, v, True, None, scale)
        else:
            mask = _attn_mask(t, t, True, None)
            out = _sdpa_full(qq, k, v, mask, scale)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(dt))
    if return_cache:
        return y, (c_kv, k_pe[:, :, 0, :])
    return y


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Compressed cache: c_kv rank-r latents + shared rope key (the MLA win)."""
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(params, x: Array, cfg: ModelConfig, cache: dict,
               pos: Array) -> tuple[Array, dict]:
    """Absorbed-matmul MLA decode: attention runs in the rank-r latent space;
    per-head K/V are never materialised for the cache."""
    dt = x.dtype
    b = x.shape[0]
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    posb = jnp.full((b, 1), pos)

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(dt))
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, posb, cfg.rope_theta)          # [B,1,H,dr]

    c_new = rmsnorm(params["ckv_norm"],
                    jnp.einsum("btd,dr->btr", x, params["w_dkv"].astype(dt)),
                    cfg.norm_eps)
    kpe_new = apply_rope(
        jnp.einsum("btd,dr->btr", x, params["w_kpe"].astype(dt))[:, :, None, :],
        posb, cfg.rope_theta)[:, :, 0, :]                  # [B,1,dr]

    ckv = lax.dynamic_update_slice(cache["ckv"], c_new.astype(cache["ckv"].dtype),
                                   (0, pos, 0))
    kpe = lax.dynamic_update_slice(cache["kpe"], kpe_new.astype(cache["kpe"].dtype),
                                   (0, pos, 0))

    # absorb w_uk into q: q_lat [B,H,r]
    q_lat = jnp.einsum("bthk,rhk->bhr", q_nope, params["w_uk"].astype(dt))
    s_nope = jnp.einsum("bhr,bsr->bhs", q_lat, ckv.astype(dt))
    s_pe = jnp.einsum("bthk,bsk->bhs", q_pe, kpe.astype(dt))
    scale = (dn + dr) ** -0.5
    s = (s_nope + s_pe).astype(jnp.float32) * scale
    valid = jnp.arange(cache["ckv"].shape[1]) <= pos
    s = s + jnp.where(valid, 0.0, NEG_INF)[None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, ckv.astype(dt))  # latent-space output
    out = jnp.einsum("bhr,rhk->bhk", o_lat, params["w_uv"].astype(dt))
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"].astype(dt))[:, None, :]
    return y, {"ckv": ckv, "kpe": kpe}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = D ** -0.5, F ** -0.5
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {
            "w_gate": jax.random.normal(k1, (D, F), jnp.float32) * s_in,
            "w_up": jax.random.normal(k2, (D, F), jnp.float32) * s_in,
            "w_down": jax.random.normal(k3, (F, D), jnp.float32) * s_out,
        }
    return {
        "w_up": jax.random.normal(k1, (D, F), jnp.float32) * s_in,
        "w_down": jax.random.normal(k2, (F, D), jnp.float32) * s_out,
    }


def mlp(params, x: Array, cfg: ModelConfig) -> Array:
    dt = x.dtype
    backend = kops.current_backend()
    if backend != "off":
        # §Kernels: the MLP matmuls on the TaxoNN kernel datapath
        if cfg.mlp_kind in ("swiglu", "geglu"):
            actk = "silu" if cfg.mlp_kind == "swiglu" else "gelu"
            g = _dense_unit(x, params["w_gate"], actk, backend)
            u = _dense_unit(x, params["w_up"], "identity", backend)
            return _dense_unit(g * u, params["w_down"], "identity", backend)
        h = _dense_unit(x, params["w_up"], "gelu", backend)
        return _dense_unit(h, params["w_down"], "identity", backend)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True)
        g = act(x @ params["w_gate"].astype(dt))
        u = x @ params["w_up"].astype(dt)
        return (g * u) @ params["w_down"].astype(dt)
    h = jax.nn.gelu(x @ params["w_up"].astype(dt), approximate=True)
    return h @ params["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# MoE (top-k routing, sort-based capacity dispatch, shared experts)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s_in, s_out = D ** -0.5, F ** -0.5
    p = {
        "router": jax.random.normal(k1, (D, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(k2, (E, D, F), jnp.float32) * s_in,
        "w_up": jax.random.normal(k3, (E, D, F), jnp.float32) * s_in,
        "w_down": jax.random.normal(k4, (E, F, D), jnp.float32) * s_out,
    }
    if cfg.num_shared_experts:
        Fs = cfg.moe_d_ff * cfg.num_shared_experts
        ks = jax.random.split(k5, 3)
        p["shared"] = {
            "w_gate": jax.random.normal(ks[0], (D, Fs), jnp.float32) * s_in,
            "w_up": jax.random.normal(ks[1], (D, Fs), jnp.float32) * s_in,
            "w_down": jax.random.normal(ks[2], (Fs, D), jnp.float32) * Fs ** -0.5,
        }
    return p


def _moe_experts_shardmap(x: Array, wg: Array, wu: Array, wd: Array,
                          slot: Array, keep: Array, sw: Array, stok: Array,
                          C: int, E: int, cfg: ModelConfig) -> Array:
    """§Perf "moe_rowcombine": the whole routed-expert path (dispatch scatter
    -> expert matmuls -> token-space combine) inside one shard_map.

    Collective profile per layer: ONE token-space psum [b,t,D] forward and
    ONE for d_tokens backward.  The pjit baseline instead reduces in
    dispatch-buffer space ([b,E,C,D], C*E ~ 1.25*K*t rows) — and its
    backward psums the buffer cotangents for w_gate AND w_up separately.

    EP (E %% model == 0): each shard scatters/computes only its experts.
    TP-inside-expert (F sharded): dispatch replicated, matmuls F-local,
    partial outputs combined then psum'd.  Routing tensors (slot/keep/sw/
    stok) are cheap and computed outside (replicated over model).
    """
    dt = x.dtype
    mesh = jax.sharding.get_abstract_mesh()
    axes = dict(mesh.shape)
    m = axes.get("model", 1)
    baxes = tuple(a for a in ("pod", "data") if a in axes)
    b_entry = baxes if len(baxes) > 1 else baxes[0]
    ep = E % m == 0 and E >= m
    if ep:
        w_in_spec = P("model", None, None)    # [E, D, F]
        wd_spec = P("model", None, None)      # [E, F, D]
    else:
        w_in_spec = P(None, None, "model")    # F sharded
        wd_spec = P(None, "model", None)
    vec = P(b_entry, None)
    x_spec = P(b_entry, None, None)

    act = jax.nn.silu if cfg.mlp_kind == "swiglu" else functools.partial(
        jax.nn.gelu, approximate=True)

    def f(x_l, wg_l, wu_l, wd_l, slot_l, keep_l, sw_l, stok_l):
        bl, t, d = x_l.shape
        e_l = wg_l.shape[0]
        if ep:
            e0 = lax.axis_index("model") * e_l
            se_l = slot_l // C                # global expert id (trash -> E)
            pos_l = slot_l - se_l * C
            keep2 = keep_l & (se_l >= e0) & (se_l < e0 + e_l)
            lslot = jnp.where(keep2, (se_l - e0) * C + pos_l, e_l * C)
        else:
            keep2 = keep_l
            lslot = jnp.where(keep_l, slot_l, e_l * C)
        rows_l = jnp.arange(bl)[:, None]
        src = jnp.take_along_axis(x_l, stok_l[..., None], axis=1)
        buf = jnp.zeros((bl, e_l * C + 1, d), dt).at[rows_l, lslot].set(src)
        buf = buf[:, :-1].reshape(bl, e_l, C, d)

        g = act(jnp.einsum("becd,edf->becf", buf, wg_l))
        u = jnp.einsum("becd,edf->becf", buf, wu_l)
        eo = jnp.einsum("becf,efd->becd", g * u, wd_l)

        gathered = eo.reshape(bl, e_l * C, -1)
        lslot_g = jnp.where(keep2, lslot, 0)
        picked = jnp.take_along_axis(gathered, lslot_g[..., None], axis=1)
        contrib = jnp.where(keep2[..., None], picked * sw_l[..., None], 0.0)
        out = jnp.zeros((bl, t, gathered.shape[-1]), dt) \
            .at[rows_l, stok_l].add(contrib.astype(dt))
        return lax.psum(out, "model")

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, w_in_spec, w_in_spec, wd_spec, vec, vec, vec, vec),
        out_specs=x_spec, check_vma=False,
    )(x, wg.astype(dt), wu.astype(dt), wd.astype(dt),
      slot, keep, sw.astype(dt), stok)


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_aux_from_stats(frac: Array, probs_mean: Array) -> Array:
    """Load-balance aux loss from its two batch-mean statistics.

    ``aux = E * sum_e frac[e] * probs_mean[e]`` is BILINEAR in two batch
    means, so it does not decompose over microbatches (the mean of
    per-microbatch aux values is NOT the full-batch aux).  Callers that
    split the batch — the stage-sharded pipeline — accumulate ``frac`` and
    ``probs_mean`` separately (``moe_verbose``), average them across
    microbatches, and recombine here to reproduce full-batch semantics.
    """
    return jnp.sum(frac * probs_mean) * frac.shape[-1]


def moe_verbose(params, x: Array, cfg: ModelConfig
                ) -> tuple[Array, Array, Array]:
    """Top-k routed MoE with PER-SEQUENCE sort-based capacity dispatch.

    Dispatch (sort, rank, scatter) happens independently per batch row along
    the last axis, so under data parallelism it is entirely local — no
    distributed sorts, no giant global dispatch buffers (a global-token sort
    at 1M tokens costs hundreds of GiB of temps and a distributed sort).
    Capacity is per sequence: C = ceil(T*K/E * capacity_factor).

    Returns (output, frac [E], probs_mean [E]) — the aux-loss statistics
    exposed separately so microbatched callers can accumulate them (see
    ``moe_aux_from_stats``); ``moe`` below contracts them to the scalar.
    """
    dt = x.dtype
    b, t, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    C = moe_capacity(cfg, t)
    nk = t * K

    logits = jnp.einsum("btd,de->bte", x, params["router"].astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, K)                    # [b,t,K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balance aux statistics (expert pick fraction, mean router prob)
    frac = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1, 2))
    probs_mean = jnp.mean(probs, axis=(0, 1))

    # ---- per-row sort-based dispatch (all ops batched over b) -----------
    flat_e = top_e.reshape(b, nk)
    flat_w = top_p.reshape(b, nk).astype(dt)

    order = jnp.argsort(flat_e, axis=-1, stable=True)      # per-row sort
    se = jnp.take_along_axis(flat_e, order, axis=-1)       # [b,nk]
    sw = jnp.take_along_axis(flat_w, order, axis=-1)
    stok = order // K                                      # source token in row

    counts = jnp.sum(flat_e[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)                               # [b,E]
    offsets = jnp.cumsum(counts, axis=-1) - counts
    pos_in_e = jnp.arange(nk)[None, :] - jnp.take_along_axis(offsets, se, -1)
    keep = pos_in_e < C

    slot = jnp.where(keep, se * C + pos_in_e, E * C)       # E*C = trash slot
    rows = jnp.arange(b)[:, None]
    act = jax.nn.silu if cfg.mlp_kind == "swiglu" else functools.partial(
        jax.nn.gelu, approximate=True)

    if perf_opt("moe_rowcombine") and model_axis_size_ctx() > 1:
        # §Perf option: dispatch + expert matmuls + combine inside one
        # shard_map -> exactly one token-space psum fwd and one bwd
        # (see _moe_experts_shardmap).
        out = _moe_experts_shardmap(
            x, params["w_gate"], params["w_up"], params["w_down"],
            slot, keep, sw, stok, C, E, cfg)
        out = constrain(out, "btd")
    else:
        src = constrain(
            jnp.take_along_axis(x, stok[..., None], axis=1), "btd")  # [b,nk,D]
        buf = jnp.zeros((b, E * C + 1, D), dt).at[rows, slot].set(src)
        # explicit batch constraint: the batched scatter otherwise leaves
        # the partitioner free to replicate the dispatch buffer over the
        # data axes (16x flops). Expert/F sharding propagates from weights.
        buf = constrain(buf[:, :-1].reshape(b, E, C, D), "becd")
        # ---- grouped expert matmuls (EP over experts when divisible) ----
        g = act(jnp.einsum("becd,edf->becf", buf, params["w_gate"].astype(dt)))
        u = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(dt))
        eo = jnp.einsum("becf,efd->becd", g * u, params["w_down"].astype(dt))
        # ---- combine back (per-row gather + weighted scatter-add) -------
        gathered = constrain(eo, "becd").reshape(b, E * C, D)
        safe_slot = jnp.where(keep, slot, 0)
        picked = constrain(
            jnp.take_along_axis(gathered, safe_slot[..., None], axis=1), "btd")
        contrib = jnp.where(keep[..., None], picked * sw[..., None], 0.0)
        out = constrain(
            jnp.zeros((b, t, D), dt).at[rows, stok].add(contrib), "btd")

    if cfg.num_shared_experts:
        sh = params["shared"]
        gs = act(x @ sh["w_gate"].astype(dt))
        us = x @ sh["w_up"].astype(dt)
        out = out + (gs * us) @ sh["w_down"].astype(dt)

    return out, frac, probs_mean


def moe(params, x: Array, cfg: ModelConfig) -> tuple[Array, Array]:
    """``moe_verbose`` with the statistics contracted to the standard
    scalar load-balance aux loss."""
    out, frac, probs_mean = moe_verbose(params, x, cfg)
    return out, moe_aux_from_stats(frac, probs_mean)
