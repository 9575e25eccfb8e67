"""Plain float32 training reference, generic over the model family.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST`` and heavy-ball
momentum SGD.  The model is the family's (``bench/families/<family>.py``):
its weights from the seed, the stage before the layer stack, one stacked
layer's forward and the head's loss.  It imports nothing of the program
and takes nothing the program made.

To fit beside nothing else on one chip it works layer by layer: the
forward keeps each layer's input, the backward runs one layer's VJP at a
time and updates that layer's weights and momentum in place.  A family
computes attention and the head in blocks, each recomputed in the
backward, so that no [B, H, T, T] or [tokens, V] tensor exists whole.

``bits`` selects the dense units' arithmetic (``dense``): ``None`` is
float32; an integer n runs every projection matmul on n-bit symmetric
per-tensor absmax operands, forward and both backward matmuls.  n = 4 is
the correctness control: the precision below the int8 that the
configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib import weights as W

HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Dense units: float32, or n-bit operands for the control
# ---------------------------------------------------------------------------

def _quant(x, bits):
    qmax = 2.0 ** (bits - 1) - 1.0
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qdot(x2, w, bits):
    return jnp.dot(_quant(x2, bits), _quant(w, bits), precision=HI)


def _qdot_fwd(x2, w, bits):
    return _qdot(x2, w, bits), (x2, w)


def _qdot_bwd(bits, res, dz):
    x2, w = res
    qz = _quant(dz, bits)
    return (jnp.dot(qz, _quant(w, bits).T, precision=HI),
            jnp.dot(_quant(x2, bits).T, qz, precision=HI))


_qdot.defvjp(_qdot_fwd, _qdot_bwd)


def dense(x, w2, bits):
    """x [..., K] @ w2 [K, N]."""
    if bits is None:
        return jnp.dot(x, w2, precision=HI)
    lead = x.shape[:-1]
    y = _qdot(x.reshape(-1, x.shape[-1]), w2, bits)
    return y.reshape(lead + (w2.shape[1],))


# ---------------------------------------------------------------------------
# Training: three (or more) momentum-SGD steps, layer by layer
# ---------------------------------------------------------------------------

def leaf_items(tree, prefix=""):
    """(name, array) for every leaf, names joined by '/'."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append((prefix + name, leaf))
    return out


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


class ReferenceTrainer:
    """Runs the plain training steps from the seed's weights and reports
    each step's loss, the per-leaf gradient norms of the first step and the
    per-leaf norms of the weights' change after the last step.  Leaves of
    the layer stack are named ``blocks/<path>/<layer>``."""

    def __init__(self, family, m: dict, lr: float, momentum: float,
                 bits=None):
        F = family
        self.fam, self.m, self.lr, self.mom, self.bits = (
            F, m, lr, momentum, bits)
        self._layer_init = jax.jit(
            lambda key, l: F.layer_params(W.layer_key(key, l), m))
        self._bnd_init = jax.jit(lambda key: F.boundary_params(key, m))

        def fwd(p, x, pos):
            return F.layer_forward(p, x, pos, m, bits)

        def bwd(p, x, pos, dy):
            _, vjp = jax.vjp(lambda pp, xx: fwd(pp, xx, pos), p, x)
            return vjp(dy)

        def head(bnd, x, labels):
            return jax.value_and_grad(
                lambda b, xx: F.loss(b, xx, labels, m), argnums=(0, 1))(bnd, x)

        def pre(bnd, tokens, pos):
            return F.pre_stack(bnd, tokens, pos, m, bits)

        def pre_bwd(bnd, tokens, pos, dx, d_bnd):
            return F.pre_stack_bwd(bnd, tokens, pos, dx, d_bnd, m, bits)

        def update(p, mo, g, lr):
            mo = jax.tree.map(lambda a, b: self.mom * a + b, mo, g)
            p = jax.tree.map(lambda a, b: a - lr * b, p, mo)
            return p, mo

        def norms(tree):
            return jax.tree.map(_norm, tree)

        def change(p, key, l):
            p0 = F.layer_params(W.layer_key(key, l), m)
            return jax.tree.map(lambda a, b: _norm(a - b), p, p0)

        def change_bnd(p, key):
            p0 = F.boundary_params(key, m)
            return jax.tree.map(lambda a, b: _norm(a - b), p, p0)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._head = jax.jit(head)
        self._pre = jax.jit(pre)
        self._pre_bwd = jax.jit(pre_bwd, donate_argnums=(4,))
        self._update = jax.jit(update, donate_argnums=(0, 1))
        self._norms = jax.jit(norms)
        self._change = jax.jit(change)
        self._change_bnd = jax.jit(change_bnd)

    def run(self, seed: int, batches: list) -> dict:
        L = self.fam.stacked_layers(self.m)
        key = W.seed_key(seed)
        layers = [self._layer_init(key, l) for l in range(L)]
        bnd = self._bnd_init(key)
        mo_layers = [jax.tree.map(jnp.zeros_like, p) for p in layers]
        mo_bnd = jax.tree.map(jnp.zeros_like, bnd)
        lr = jnp.float32(self.lr)
        losses, grad_norms = [], {}
        for step, batch in enumerate(batches):
            tokens = jnp.asarray(batch["tokens"])
            labels = jnp.asarray(batch["labels"])
            pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
            x = self._pre(bnd, tokens, pos)
            xs = []
            for p in layers:
                xs.append(x)
                x = self._fwd(p, x, pos)
            loss, (d_bnd, dx) = self._head(bnd, x, labels)
            losses.append(float(loss))
            for l in reversed(range(L)):
                d_p, dx = self._bwd(layers[l], xs[l], pos, dx)
                xs[l] = None
                if step == 0:
                    for name, v in leaf_items(self._norms(d_p), "blocks/"):
                        grad_norms[f"{name}/{l}"] = float(v)
                layers[l], mo_layers[l] = self._update(
                    layers[l], mo_layers[l], d_p, lr)
            d_bnd = self._pre_bwd(bnd, tokens, pos, dx, d_bnd)
            if step == 0:
                for name, v in leaf_items(self._norms(d_bnd)):
                    grad_norms[name] = float(v)
            bnd, mo_bnd = self._update(bnd, mo_bnd, d_bnd, lr)
        change = {}
        for l in range(L):
            for name, v in leaf_items(self._change(layers[l], key, l),
                                      "blocks/"):
                change[f"{name}/{l}"] = float(v)
        for name, v in leaf_items(self._change_bnd(bnd, key)):
            change[name] = float(v)
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}
