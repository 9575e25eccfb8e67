"""Cross-replica gradient reduction: dense and int8-compressed tree psum.

``dense_psum_tree`` is the reference f32 all-reduce.  ``compressed_psum_tree``
is the ICI-bytes analogue of TaxoNN's low-bitwidth MACs: each replica
block-scales its gradient to int8 (repro.quant.compression), the *compressed*
payload+scales travel over the interconnect (all-gather), and every replica
decompresses and sums locally.  1 byte/element + 4/BLOCK scale overhead vs 4
bytes/element dense — the Table-IV byte reduction applied to the dW
all-reduce that the backward scan issues per layer.

Both functions treat the input tree as *per-replica* values laid out
replicated on the mesh and return the elementwise sum across the named axes
(identical on every replica).  The compressed variant's error is bounded by
one quantization step per replica: |err| <= n_replicas * absmax_block / 127
/ 2 per element.
"""
from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.quant.compression import compress_int8, decompress_int8


def _reduce_size(mesh, axes) -> int:
    shape = dict(mesh.shape)
    n = 1
    for a in axes:
        n *= shape[a]
    return n


def dense_psum_tree(grads, mesh, axes: Iterable[str]):
    """Elementwise sum of ``grads`` across the mesh axes ``axes``."""
    axes = tuple(axes)

    def f(tree):
        return jax.tree.map(lambda x: lax.psum(x, axes), tree)

    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(grads)


def compressed_psum(x, axes: Iterable[str] = (), num_replicas: int = None):
    """One-tensor int8 block-scaled all-reduce (the dW wire format).

    The public per-tensor entry point: the TaxoNN engine's backward scan
    calls it per layer (``QuantPolicy.compress_dw``) and
    ``compressed_psum_tree`` maps it over a gradient tree inside its own
    shard_map.  With ``axes`` naming mesh axes it must run where those
    axes are bound (a shard_map body) and moves the compressed
    payload+scales over the interconnect.  With empty axes it is the pure
    codec round-trip — the wire-format numerics with no collective — and
    honors ``num_replicas`` as the simulated reduction size: ``n``
    replicas of a replicated value sum to ``n * decompress(compress(x))``,
    matching what the mesh path returns for the same replicated input.
    """
    axes = tuple(axes)
    payload, scales = compress_int8(x)
    if not axes or num_replicas == 1:
        dec = decompress_int8(payload, scales, x.shape, x.dtype)
        if not axes and num_replicas is not None and num_replicas > 1:
            dec = (dec.astype(jnp.float32) * num_replicas).astype(x.dtype)
        return dec
    pg = lax.all_gather(payload, axes)   # [n, N] int8 on the wire
    sg = lax.all_gather(scales, axes)    # [n, N/BLOCK] f32
    dec = jax.vmap(
        lambda p, s: decompress_int8(p, s, x.shape, jnp.float32)
    )(pg, sg)
    return jnp.sum(dec, axis=0).astype(x.dtype)


def compressed_psum_tree(grads, mesh, axes: Iterable[str]):
    """int8 block-scaled all-reduce: compress locally, move compressed
    bytes, decompress + sum on every replica."""
    axes = tuple(axes)
    n = _reduce_size(mesh, axes)

    def f(tree):
        return jax.tree.map(
            lambda x: compressed_psum(x, axes, num_replicas=n), tree)

    return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(grads)
