"""Weights made from the seed, in the program's parameter layout.

The benchmark makes the weights itself, so the plain reference can make
the same ones again (layer by layer, from the seed) without taking
anything the program produced.  Layer ``l`` comes from
``fold_in(key(seed), 1000 + l)``, the embedding from ``fold_in(., 0)``,
an untied head [D, V] from ``fold_in(., 1)``; the stacked ``[L, ...]`` tree is the same per-layer draws under ``vmap``.
Scales follow the usual fan-in rule; qkv biases get small nonzero values
so that their path is exercised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.flops import dims

BIAS_SCALE = 0.1


def seed_key(seed: int):
    """A JAX key from any whole-number seed (seeds beyond 32 bits are
    mixed down by numpy's SeedSequence, never truncated)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word)


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


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


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


def stacked_params(key, m: dict) -> dict:
    """The whole model, layers stacked on a leading axis (program layout).
    Call it inside ``jit`` so that it is made on the device."""
    params = boundary_params(key, m)
    params["blocks"] = jax.vmap(
        lambda l: layer_params(layer_key(key, l), m))(
            jnp.arange(dims(m)["L"]))
    return params
