"""Plain float32 reference of the dense decoder family.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: RMSNorm, rotary
embedding (half rotation), grouped-query causal attention with an optional
sliding window, SwiGLU MLP, a tied or untied head, mean cross-entropy,
and heavy-ball momentum SGD.  It imports nothing of the program and makes
its weights from the seed (``bench.lib.weights``).

To fit beside nothing else on one chip it works layer by layer: the
forward keeps each layer's input, the backward runs one layer's VJP at a
time and updates that layer's weights and momentum in place.  Attention is
computed in blocks of query rows and the head in blocks of tokens, each
recomputed in the backward, so no [B, H, T, T] or [tokens, V] tensor
exists whole.

``bits`` selects the dense units' arithmetic: ``None`` is float32; an
integer n runs every projection matmul (q, k, v, o, gate, up, down) on
n-bit symmetric per-tensor absmax operands, forward and both backward
matmuls.  n = 4 is the correctness control: the precision below the int8
that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib import weights as W
from bench.lib.flops import dims

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512
HEAD_BLOCK = 1024


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
# Layers
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def rope(x, pos, theta):
    """x [B, T, H, hd], pos [B, T]: rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v, pos, window):
    """Causal GQA attention in blocks of query rows.
    q [B, T, H, hd]; k, v [B, T, Hkv, hd]; pos [B, T] absolute positions."""
    b, t, h, hd = q.shape
    hkv = k.shape[2]
    qb = min(QUERY_BLOCK, t)
    n = -(-t // qb)
    pad = n * qb - t
    qg = q.reshape(b, t, hkv, h // hkv, hd)
    qpos = pos
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(pos, ((0, 0), (0, pad)))
    qg = qg.reshape(b, n, qb, hkv, h // hkv, hd).transpose(1, 0, 2, 3, 4, 5)
    qpos = qpos.reshape(b, n, qb).transpose(1, 0, 2)

    @jax.checkpoint
    def block(args):
        qq, qp = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qq, k, precision=HI) * hd ** -0.5
        ok = pos[:, None, :] <= qp[:, :, None]                 # [b, q, s]
        if window is not None:
            ok &= pos[:, None, :] > qp[:, :, None] - window
        s = jnp.where(ok[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)

    out = lax.map(block, (qg, qpos))                  # [n, b, qb, hkv, g, hd]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, n * qb, h, hd)
    return out[:, :t]


def layer_forward(p, x, pos, m, bits=None):
    """One decoder block: x + attn(norm(x)), then + mlp(norm(.))."""
    d = dims(m)
    D, H, Hkv, hd = d["D"], d["H"], d["Hkv"], d["hd"]
    eps = m["norm_eps"]
    a = p["attn"]
    h = rmsnorm(x, p["attn_norm"]["scale"], eps)
    q = dense(h, a["wq"].reshape(D, H * hd), bits).reshape(h.shape[:2] + (H, hd))
    k = dense(h, a["wk"].reshape(D, Hkv * hd), bits).reshape(h.shape[:2] + (Hkv, hd))
    v = dense(h, a["wv"].reshape(D, Hkv * hd), bits).reshape(h.shape[:2] + (Hkv, hd))
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    o = attend(q, k, v, pos, m.get("swa_window"))
    x = x + dense(o.reshape(o.shape[:2] + (H * hd,)), a["wo"].reshape(H * hd, D), bits)
    mp = p["mlp"]
    h = rmsnorm(x, p["mlp_norm"]["scale"], eps)
    g = jax.nn.silu(dense(h, mp["w_gate"], bits))
    u = dense(h, mp["w_up"], bits)
    return x + dense(g * u, mp["w_down"], bits)


def head_loss(w, scale, x, labels, eps):
    """Mean cross-entropy of the head ``w`` [D, V] over all tokens, in
    blocks of tokens recomputed in the backward."""
    xs = x.reshape(-1, x.shape[-1])
    ls = labels.reshape(-1)
    n = xs.shape[0]
    blk = min(HEAD_BLOCK, n)
    if n % blk:
        raise ValueError(f"{n} tokens do not split into blocks of {blk}")
    xs = xs.reshape(n // blk, blk, -1)
    ls = ls.reshape(n // blk, blk)

    @jax.checkpoint
    def body(tot, args):
        xb, lb = args
        logits = jnp.dot(rmsnorm(xb, scale, eps), w, precision=HI)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(lse - tgt), None

    tot, _ = lax.scan(body, jnp.float32(0.0), (xs, ls))
    return tot / n


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

    def __init__(self, m: dict, lr: float, momentum: float, bits=None):
        self.m, self.lr, self.mom, self.bits = m, lr, momentum, bits
        eps = m["norm_eps"]
        self._layer_init = jax.jit(
            lambda key, l: W.layer_params(W.layer_key(key, l), m))
        self._bnd_init = jax.jit(lambda key: W.boundary_params(key, m))

        def fwd(p, x, pos):
            return layer_forward(p, x, pos, m, bits)

        def bwd(p, x, pos, dy):
            _, vjp = jax.vjp(lambda pp, xx: fwd(pp, xx, pos), p, x)
            return vjp(dy)

        def head(bnd, x, labels):
            return jax.value_and_grad(
                lambda b, xx: head_loss(W.head_weight(b),
                                        b["final_norm"]["scale"], xx, labels,
                                        eps),
                argnums=(0, 1))(bnd, x)

        def embed_grad(d_embed, tokens, dx0):
            return d_embed.at[tokens.reshape(-1)].add(
                dx0.reshape(-1, dx0.shape[-1]))

        def update(p, mo, g, lr):
            mo = jax.tree.map(lambda a, b: self.mom * a + b, mo, g)
            p = jax.tree.map(lambda a, b: a - lr * b, p, mo)
            return p, mo

        def norms(tree):
            return jax.tree.map(_norm, tree)

        def change(p, key, l):
            p0 = W.layer_params(W.layer_key(key, l), m)
            return jax.tree.map(lambda a, b: _norm(a - b), p, p0)

        def change_bnd(p, key):
            p0 = W.boundary_params(key, m)
            return jax.tree.map(lambda a, b: _norm(a - b), p, p0)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._head = jax.jit(head)
        self._embed_grad = jax.jit(embed_grad, donate_argnums=(0,))
        self._update = jax.jit(update, donate_argnums=(0, 1))
        self._norms = jax.jit(norms)
        self._change = jax.jit(change)
        self._change_bnd = jax.jit(change_bnd)

    def run(self, seed: int, batches: list) -> dict:
        L = dims(self.m)["L"]
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
            x = bnd["embed"][tokens]
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
            d_bnd = dict(d_bnd)
            d_bnd["embed"] = self._embed_grad(d_bnd["embed"], tokens, dx)
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
