"""Weights made from the seed, in the program's parameter layout.

The benchmark makes the weights itself, so the plain reference can make
the same ones again (layer by layer, from the seed) without taking
anything the program produced.  What a layer and the boundary hold is the
model family's (``bench/families/<family>.py``); stacked layer ``l`` is
drawn from ``layer_key(key, l)``, and the stacked ``[L, ...]`` tree is the
same per-layer draws under ``vmap``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A JAX key from any whole-number seed (seeds beyond 32 bits are
    mixed down by numpy's SeedSequence, never truncated)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word)


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


def stacked_params(key, m: dict, family) -> dict:
    """The whole model, layers stacked on a leading axis (program layout).
    Call it inside ``jit`` so that it is made on the device."""
    params = family.boundary_params(key, m)
    params["blocks"] = jax.vmap(
        lambda l: family.layer_params(layer_key(key, l), m))(
            jnp.arange(family.stacked_layers(m)))
    return params
