"""Layer names on the train step's device ops.

Every op the step traces carries, in its HLO ``op_name`` metadata, the
``jax.named_scope`` path it was traced under.  The innermost name of this
table on that path says which layer of the model the op belongs to:

  embed       the token embedding and its input-side gradient
  block       one layer's forward, and its recompute inside the G-chain
  attention   scores, mask, softmax and P @ V (not the projections)
  dense_unit  quantize in, kernel, rescale out; its dx and dW legs
  head_loss   final norm, logits, cross-entropy and their backward
  gchain      one layer's VJP and G quantization in the backward scan
  update      the dW reduce, momentum and weight update

The scopes nest (``attention`` and ``dense_unit`` under ``block``, ``block``
under ``gchain`` during the recompute).  They only write metadata: the
compiled program is the same with or without them.
"""
from __future__ import annotations

import functools

import jax

LAYER_SCOPES = ("embed", "block", "attention", "dense_unit", "head_loss",
                "gchain", "update")


def scoped(name: str, fn):
    """``fn`` with every op it traces named under the scope ``name``."""
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return inner
