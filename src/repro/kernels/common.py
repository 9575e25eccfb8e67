"""Shared helpers for the TaxoNN Pallas kernels.

In-kernel fixed-point quantization (pure ops — no custom_vjp: the TaxoNN
engine owns gradients explicitly, kernels are forward pieces) and the
activation-derivative unit (the paper's f' hardware block).

TPU notes: block shapes are chosen 128-aligned for the MXU; accumulation is
f32 in VMEM (the paper's wide accumulator registers).  On real TPU the
(I,F)<=8-bit formats map to the int8 MXU path; this emulation computes the
same values in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def db_step(k, n_k: int, dmas):
    """One step of the shared double-buffer protocol: start the k==0
    copies, prefetch block k+1 into the other slot, wait on block k's, and
    return the slot (k % 2) the caller should consume.  ``dmas`` is a
    sequence of ``dma(slot, kk)`` constructors (one per streamed operand);
    the copy started here at step k is the one waited at step k+1, giving
    one grid step of DMA/compute overlap per operand."""
    @pl.when(k == 0)
    def _first():
        for d in dmas:
            d(0, 0).start()

    @pl.when(k + 1 < n_k)
    def _prefetch():
        nxt = (k + 1) % 2
        for d in dmas:
            d(nxt, k + 1).start()

    slot = k % 2
    for d in dmas:
        d(slot, k).wait()
    return slot


def kq(x, i_bits: int, f_bits: int):
    """Round-to-nearest fixed-point quantize (static bits inside a kernel)."""
    step = jnp.float32(2.0 ** (-f_bits))
    qmax = jnp.float32(2.0 ** (i_bits + f_bits) - 1)
    qmin = jnp.float32(-(2.0 ** (i_bits + f_bits)))
    k = jnp.clip(jnp.round(x.astype(jnp.float32) / step), qmin, qmax)
    return k * step


def maybe_kq(x, bits):
    """kq with ``bits=None`` meaning passthrough (unquantized datapath)."""
    return x if bits is None else kq(x, *bits)


def int8_dot(a, b, dims=None):
    """int8 x int8 -> int32 MAC: the MXU low-bit path (paper's PE array).

    ``dims`` follows ``lax.dot_general`` dimension_numbers; default is a
    plain [M,K]x[K,N] matmul.  Accumulation is exact int32 (the paper's
    wide accumulator registers — no rounding until the final rescale).
    The precision is pinned to DEFAULT: integer MACs are exact at any
    precision, and Mosaic refuses an int8 matmul carrying the fp32 contract
    precision that ``jax_default_matmul_precision="highest"`` would stamp.
    """
    dims = dims or (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.int32)


_GELU_C = 0.7978845608028654  # sqrt(2/pi)
_GELU_A = 0.044715


def act_fn(z, kind: str):
    if kind == "relu":
        return jnp.maximum(z, 0.0)
    if kind == "sigmoid":
        return 1.0 / (1.0 + jnp.exp(-z))
    if kind == "tanh":
        return jnp.tanh(z)
    if kind == "silu":
        return z / (1.0 + jnp.exp(-z))
    if kind == "gelu":  # tanh approximation (matches jax.nn.gelu approximate)
        return 0.5 * z * (1.0 + jnp.tanh(_GELU_C * (z + _GELU_A * z * z * z)))
    if kind == "identity":
        return z
    raise ValueError(kind)


def act_deriv(z, kind: str):
    """The paper's activation-derivation unit: f'(z) from the pre-activation.

    sigma' = sigma(1-sigma); tanh' = 4*sigma'(2z); relu' = step(z)."""
    if kind == "relu":
        return (z > 0).astype(jnp.float32)
    if kind == "sigmoid":
        s = 1.0 / (1.0 + jnp.exp(-z))
        return s * (1.0 - s)
    if kind == "tanh":
        t = jnp.tanh(z)
        return 1.0 - t * t
    if kind == "silu":
        s = 1.0 / (1.0 + jnp.exp(-z))
        return s * (1.0 + z * (1.0 - s))
    if kind == "gelu":
        u = _GELU_C * (z + _GELU_A * z * z * z)
        t = jnp.tanh(u)
        du = _GELU_C * (1.0 + 3.0 * _GELU_A * z * z)
        return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * du
    if kind == "identity":
        return jnp.ones_like(z)
    raise ValueError(kind)
