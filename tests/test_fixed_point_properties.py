"""Property tests for the fixed-point (I,F) quantizers.

Runs under hypothesis with the suite's derandomized profile (see
conftest.py).  Each property is the algebraic contract the search/anneal/export
subsystem builds on:

  * idempotence — a value already on the (I,F) grid is a fixed point of
    ``quantize`` (the sweep re-quantizes cached activations freely);
  * saturation — out-of-range values clip to exactly +/- the format
    bounds (the export path's int8 embedding assumes the same clip);
  * STE — forward equals ``quantize``, backward passes gradients through
    in-range inputs and masks saturated ones;
  * stochastic rounding — per-row batched draws are mean-unbiased within
    a seeded tolerance (what keeps low-F gradient descent convergent).
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.quant.fixed_point import (fxp_max, fxp_resolution, quantize,
                                     quantize_ste, stochastic_round_batched)

jax.config.update("jax_default_matmul_precision", "highest")

BITS = st.tuples(st.integers(1, 4), st.integers(2, 12))  # (I, F)


@settings(max_examples=30, deadline=None)
@given(bits=BITS, k=st.integers(-1024, 1023))
def test_quantize_idempotent_on_grid(bits, k):
    i_b, f_b = bits
    # clamp k into the format's integer range so x starts ON the grid
    lo, hi = -(2 ** (i_b + f_b)), 2 ** (i_b + f_b) - 1
    k = int(np.clip(k, lo, hi))
    x = jnp.float32(k) * fxp_resolution(f_b)
    q = quantize(x, i_b, f_b)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(x))
    # and quantize o quantize == quantize for arbitrary inputs
    y = jnp.float32(k) * 0.137
    np.testing.assert_array_equal(
        np.asarray(quantize(quantize(y, i_b, f_b), i_b, f_b)),
        np.asarray(quantize(y, i_b, f_b)))


@settings(max_examples=30, deadline=None)
@given(bits=BITS, mag=st.floats(1.0, 100.0, width=32))
def test_quantize_saturates_at_fxp_max(bits, mag):
    i_b, f_b = bits
    bound = float(fxp_max(i_b, f_b))
    step = float(fxp_resolution(f_b))
    x = jnp.float32(bound + mag)  # beyond the positive edge
    np.testing.assert_allclose(float(quantize(x, i_b, f_b)), bound, rtol=0)
    # negative side clips one step lower (two's-complement asymmetry)
    np.testing.assert_allclose(float(quantize(-x, i_b, f_b)),
                               -(bound + step), rtol=0)


@settings(max_examples=30, deadline=None)
@given(bits=BITS, x=st.floats(-40.0, 40.0, width=32))
def test_ste_forward_matches_quantize(bits, x):
    i_b, f_b = bits
    xj = jnp.float32(x)
    np.testing.assert_array_equal(
        np.asarray(quantize_ste(xj, jnp.int32(i_b), jnp.int32(f_b))),
        np.asarray(quantize(xj, i_b, f_b)))


@settings(max_examples=30, deadline=None)
@given(bits=BITS, x=st.floats(-40.0, 40.0, width=32))
def test_ste_gradient_passthrough_and_mask(bits, x):
    i_b, f_b = bits
    xj = jnp.float32(x)
    g = jax.grad(
        lambda v: jnp.sum(quantize_ste(v, jnp.int32(i_b), jnp.int32(f_b))))(xj)
    in_range = abs(x) <= float(fxp_max(i_b, f_b))
    np.testing.assert_array_equal(np.asarray(g),
                                  np.float32(1.0 if in_range else 0.0))


@settings(max_examples=10, deadline=None)
@given(bits=st.tuples(st.integers(2, 4), st.integers(3, 8)),
       seed=st.integers(0, 1000))
def test_stochastic_round_batched_mean_unbiased(bits, seed):
    i_b, f_b = bits
    # a value mid-way between grid points, repeated across many rows:
    # E[q(x)] = x for in-range x, so the per-row mean converges on x
    step = float(fxp_resolution(f_b))
    x_val = 0.5 + 0.3 * step
    rows = 4096
    x = jnp.full((rows, 4), x_val, jnp.float32)
    q = stochastic_round_batched(x, jnp.int32(i_b), jnp.int32(f_b),
                                 jax.random.key(seed), 0)
    # each draw is one of the two neighbours
    lo, hi = np.floor(x_val / step) * step, np.ceil(x_val / step) * step
    vals = np.unique(np.asarray(q))
    assert all(np.isclose(v, lo, atol=1e-6) or np.isclose(v, hi, atol=1e-6)
               for v in vals), vals
    # mean unbiasedness: SE of the mean is step/2/sqrt(n); allow 5 sigma
    tol = 5 * step / 2 / np.sqrt(rows * 4)
    assert abs(float(jnp.mean(q)) - x_val) < tol


@settings(max_examples=10, deadline=None)
@given(bits=st.tuples(st.integers(2, 4), st.integers(3, 8)),
       seed=st.integers(0, 1000))
def test_stochastic_round_batched_slice_reproducible(bits, seed):
    """Slicing the batch and passing the slice's offset reproduces the
    full-batch draws (the pipeline-vs-scan conformance contract)."""
    i_b, f_b = bits
    key = jax.random.key(seed)
    x = jax.random.normal(jax.random.key(seed + 1), (8, 3), jnp.float32)
    full = stochastic_round_batched(x, jnp.int32(i_b), jnp.int32(f_b), key, 0)
    part = stochastic_round_batched(x[3:], jnp.int32(i_b), jnp.int32(f_b),
                                    key, 3)
    np.testing.assert_array_equal(np.asarray(full[3:]), np.asarray(part))
