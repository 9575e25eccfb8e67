"""The dense decoder family: its weights, its plain reference and its counts.

A family module is what the generic harness (``bench/lib``) asks of a model
family, found by the configuration's ``family`` (``Spec.family``).  Its
interface, with the layer of the program each hook stands for:

  weights (``bench/lib/weights.py`` stacks and draws them)
    boundary_params(key, m)     everything outside the layer stack: the
                                embedding, the final norm, an untied head,
                                and any layer that runs before the stack
    layer_params(key, m)        one stacked layer
    stacked_layers(m)           how many layers the stack holds
    head_weight(bnd)            the head's [D, V] weight
  reference (``bench/lib/reference.py`` runs them layer by layer)
    pre_stack(bnd, tokens, pos, m, bits) -> x      embed (the stage before
                                                   the stack)
    pre_stack_bwd(bnd, tokens, pos, dx, d_bnd, m, bits) -> d_bnd
                                adds that stage's gradient into ``d_bnd``
                                (which holds the head's)
    layer_forward(p, x, pos, m, bits) -> x         one block
    loss(bnd, x, labels, m) -> mean cross-entropy  head and loss
  counts (``bench/lib/train_loop.py``, ``bench/metrics``)
    train_flops_per_step(m, batch, seq)            train step, ``mfu``
    dense_unit_grids(m, rows)                      the int8 dense units
    param_count(m)                                 all parameters (a check)
    matmul_params(m)                               weights a token
                                                   multiplies (a check)

``param_count`` equals the program's ``param_count()``.  ``matmul_params``
counts the weights one token multiplies: each layer's projections, of the
experts only the active ones (routed top-k and shared), and the head once;
no norm scale, bias or input-table lookup.  ``tests/bench/test_bench_flops.py``
holds it to the program's ``active_param_count()`` less the leaves of
``stacked_params`` that are norms or biases by name (a path part ending in
``norm``, or a last part ``bq``, ``bk``, ``bv`` or ``bias``), so a family
names those leaves so.

Here: RMSNorm, rotary embedding (half rotation), grouped-query causal
attention with an optional sliding window, SwiGLU MLP, a tied or untied
head, in float32 at ``Precision.HIGHEST``.  Weights: layer ``l`` comes from
``layer_key`` (``fold_in(key(seed), 1000 + l)``), the embedding from
``fold_in(., 0)``, an untied head [D, V] from ``fold_in(., 1)``.  Scales
follow the usual fan-in rule; qkv biases get small nonzero values so that
their path is exercised.

Model FLOPs count the work the model requires, whatever implements it: 2
per multiply-add of every weight matmul (the non-embedding weights plus
the V x D head), plus attention's two score/value matmuls over the keys
the causal (and window) mask admits.  A trained token costs three forward
passes' worth; recomputation is not counted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib.flops import keys_attended
from bench.lib.reference import HI, dense

BIAS_SCALE = 0.1
QUERY_BLOCK = 512       # query rows of one attention block
HEAD_BLOCK = 1024       # tokens of one block of the head's logits


# ---------------------------------------------------------------------------
# Sizes and counts
# ---------------------------------------------------------------------------

def dims(m: dict) -> dict:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return {"D": m["d_model"], "H": m["num_heads"], "Hkv": m["num_kv_heads"],
            "hd": hd, "F": m["d_ff"], "V": m["vocab_size"],
            "L": m["num_layers"]}


def dense_shapes(m: dict) -> list:
    """(K, N) of each dense unit x @ w in one layer, in program order:
    q, k, v, o projections, then the MLP's gate, up and down."""
    d = dims(m)
    D, H, Hkv, hd, F = d["D"], d["H"], d["Hkv"], d["hd"], d["F"]
    attn = [(D, H * hd), (D, Hkv * hd), (D, Hkv * hd), (H * hd, D)]
    gated = m.get("mlp_kind", "swiglu") in ("swiglu", "geglu")
    mlp = [(D, F), (D, F), (F, D)] if gated else [(D, F), (F, D)]
    return attn + mlp


def matmul_params(m: dict) -> int:
    """Weights that multiply activations per token: every layer's dense
    units plus the V x D head (tied or not)."""
    d = dims(m)
    per_layer = sum(k * n for k, n in dense_shapes(m))
    return d["L"] * per_layer + d["V"] * d["D"]


def param_count(m: dict) -> int:
    """All parameters, counted as the model holds them (norm scales, qkv
    biases, one or two embedding tables)."""
    d = dims(m)
    per_layer = sum(k * n for k, n in dense_shapes(m)) + 2 * d["D"]
    if m.get("qkv_bias"):
        per_layer += (d["H"] + 2 * d["Hkv"]) * d["hd"]
    emb = d["V"] * d["D"] * (1 if m.get("tie_embeddings", True) else 2)
    return d["L"] * per_layer + emb + d["D"]


def attention_flops(m: dict, keys: int) -> int:
    """Forward FLOPs of QK^T and PV over ``keys`` attended (query, key)
    pairs, summed over layers."""
    d = dims(m)
    return 2 * 2 * d["H"] * d["hd"] * keys * d["L"]


def forward_flops(m: dict, tokens: int, keys: int) -> int:
    return 2 * matmul_params(m) * tokens + attention_flops(m, keys)


def train_flops_per_step(m: dict, batch: int, seq: int) -> int:
    keys = batch * keys_attended(0, seq, m.get("swa_window"))
    return 3 * forward_flops(m, batch * seq, keys)


def dense_unit_grids(m: dict, rows: int) -> list:
    """(M, N, K) of every dense-unit matmul grid in one training step over
    ``rows`` tokens: x @ w, dz @ w^T and x^T @ dz of each layer's units."""
    grids = []
    for k, n in dense_shapes(m):
        grids += [(rows, n, k), (rows, k, n), (k, n, rows)]
    return grids * dims(m)["L"]


# ---------------------------------------------------------------------------
# Weights from the seed, in the program's parameter layout
# ---------------------------------------------------------------------------

def layer_params(key, m: dict) -> dict:
    d = dims(m)
    D, H, Hkv, hd, F = d["D"], d["H"], d["Hkv"], d["hd"], d["F"]
    ks = jax.random.split(key, 10)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    attn = {"wq": normal(ks[0], (D, H, hd), D ** -0.5),
            "wk": normal(ks[1], (D, Hkv, hd), D ** -0.5),
            "wv": normal(ks[2], (D, Hkv, hd), D ** -0.5),
            "wo": normal(ks[3], (H, hd, D), (H * hd) ** -0.5)}
    if m.get("qkv_bias"):
        attn["bq"] = normal(ks[4], (H, hd), BIAS_SCALE)
        attn["bk"] = normal(ks[5], (Hkv, hd), BIAS_SCALE)
        attn["bv"] = normal(ks[6], (Hkv, hd), BIAS_SCALE)
    mlp = {"w_gate": normal(ks[7], (D, F), D ** -0.5),
           "w_up": normal(ks[8], (D, F), D ** -0.5),
           "w_down": normal(ks[9], (F, D), F ** -0.5)}
    ones = jnp.ones((D,), jnp.float32)
    return {"attn_norm": {"scale": ones}, "mlp_norm": {"scale": ones},
            "attn": attn, "mlp": mlp}


def stacked_layers(m: dict) -> int:
    return m["num_layers"]


def boundary_params(key, m: dict) -> dict:
    d = dims(m)
    embed = jax.random.normal(jax.random.fold_in(key, 0), (d["V"], d["D"]),
                              jnp.float32) * d["D"] ** -0.5
    out = {"embed": embed,
           "final_norm": {"scale": jnp.ones((d["D"],), jnp.float32)}}
    if not m.get("tie_embeddings", True):
        out["lm_head"] = jax.random.normal(
            jax.random.fold_in(key, 1), (d["D"], d["V"]),
            jnp.float32) * d["D"] ** -0.5
    return out


def head_weight(bnd: dict):
    """The head's [D, V] weight: the untied head, else the embedding."""
    return bnd["lm_head"] if "lm_head" in bnd else bnd["embed"].T


# ---------------------------------------------------------------------------
# The plain reference
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


def loss(bnd, x, labels, m):
    return head_loss(head_weight(bnd), bnd["final_norm"]["scale"], x, labels,
                     m["norm_eps"])


def pre_stack(bnd, tokens, pos, m, bits=None):
    """The token embedding."""
    return bnd["embed"][tokens]


def pre_stack_bwd(bnd, tokens, pos, dx, d_bnd, m, bits=None):
    """The embedding's gradient: dx scatter-added into the head's."""
    d_bnd = dict(d_bnd)
    d_bnd["embed"] = d_bnd["embed"].at[tokens.reshape(-1)].add(
        dx.reshape(-1, dx.shape[-1]))
    return d_bnd
