"""The Pallas flash-attention kernels against the materialised path.

The kernels run in the Pallas interpreter here at small shapes and are
compared, output and Q/K/V gradients, with ``layers._sdpa_full`` in f32 at
HIGHEST precision: heads packed two to a lane tile (MHA, head 64), padded
to 128 lanes (GQA, heads 64 and 120), and sliding windows.  The gate cases
check that ``layers.attention`` keeps today's path, bit for bit, wherever
the kernel is not taken, and that it traces the kernel where it is.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models.config import ModelConfig

jax.config.update("jax_default_matmul_precision", "highest")

# Worst |kernel - f32 reference| over the reference's largest entry, for
# bf16 Q/K/V: a few bf16 roundings (eps 2**-8) of the largest entry.
BF16_TOL = 2e-2


def _f32_reference(q, k, v, scale, window):
    t, groups = q.shape[1], q.shape[2] // k.shape[2]
    f = lambda x: x.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return L._sdpa_full(f(q), f(L._expand_kv(k, groups)),
                            f(L._expand_kv(v, groups)),
                            L._attn_mask(t, t, True, window), scale)


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("b,t,h,hkv,hd,window", [
    (2, 256, 4, 4, 64, None),      # MHA, head 64: two heads a lane tile
    (1, 512, 4, 4, 64, None),
    (1, 256, 4, 2, 64, None),      # GQA 2:1, head 64 padded to 128
    (1, 256, 8, 2, 120, None),     # GQA 4:1, head 120 padded to 128
    (1, 512, 8, 2, 120, None),
    (1, 512, 8, 2, 120, 200),      # a window that cuts keys
    (2, 256, 4, 4, 64, 256),       # a window that cuts none
], ids=["mha64-t256", "mha64-t512", "gqa64-t256", "gqa120-t256",
        "gqa120-t512", "gqa120-t512-window200", "mha64-t256-window256"])
def test_flash_matches_f32_reference(b, t, h, hkv, hd, window):
    ks = jax.random.split(jax.random.key(t + hd), 4)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, hkv, hd), jnp.bfloat16)
    do = jax.random.normal(ks[3], (b, t, h, hd), jnp.bfloat16)
    scale = hd ** -0.5

    out, vjp = jax.vjp(lambda q, k, v: FA.flash_attention(
        q, k, v, scale=scale, window=window, interpret=True), q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: _f32_reference(q, k, v, scale, window), q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               (out, *vjp(do)),
                               (ref, *vjp_ref(do.astype(jnp.float32)))):
        assert got.shape == want.shape, name
        assert _rel_err(got, want) < BF16_TOL, name


def _cfg(**kw):
    base = dict(name="t-flash", family="dense", num_layers=1, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _todays_attention(params, x, cfg, positions, causal):
    """``layers.attention`` as it reads without the kernel."""
    q, k, v = L._project_qkv(params, x, cfg, positions)
    groups = q.shape[2] // cfg.num_kv_heads
    t = x.shape[1]
    out = L._sdpa_full(q, L._expand_kv(k, groups), L._expand_kv(v, groups),
                       L._attn_mask(t, t, causal, cfg.swa_window),
                       cfg.head_dim ** -0.5)
    return jnp.einsum("bthk,hkd->btd", out, L._masked_wo(params, cfg, x.dtype))


@pytest.mark.parametrize("t,causal,reason", [
    (256, True, "interpret"),      # aligned and causal, but no TPU
    (200, True, "unaligned"),
    (256, False, "bidirectional"),
])
def test_attention_keeps_todays_path_off_the_kernel(t, causal, reason):
    cfg = _cfg()
    params = L.init_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, t, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(t), (2, t))
    kops.KERNEL_TRACES.clear()
    got = jax.jit(lambda p, x: L.attention(p, x, cfg, positions,
                                           causal=causal))(params, x)
    assert kops.KERNEL_TRACES == {("flash_attention", reason): 1}
    want = jax.jit(lambda p, x: _todays_attention(p, x, cfg, positions,
                                                  causal))(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_attention_takes_the_kernel_on_tpu(monkeypatch):
    """With the platform's answer turned to TPU, causal attention over an
    aligned T traces the three kernels (forward and both backward)."""
    monkeypatch.setattr(kops, "interpret_mode", lambda kernel: False)
    cfg = _cfg(head_dim=120, swa_window=4096)
    params = L.init_attention(jax.random.key(0), cfg)
    x = jnp.zeros((1, 256, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(256), (1, 256))
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: L.attention(p, x, cfg, positions).sum()))(params))
    for name in ("flash_attention", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert f"name={name}\n" in jaxpr or f"name={name} " in jaxpr, name
