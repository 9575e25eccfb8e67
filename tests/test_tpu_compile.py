"""AOT compiles of the Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (scalars read from an
``ANY`` memory-space ref, tiles whose temporaries overflow VMEM), so every
kernel on the train and serve hot paths is compiled here against a
described ``v5e:2x2`` topology at the shapes those paths use, and the
compiled text must hold the Mosaic call (``tpu_custom_call``).  Nothing
runs: a passing compile is not a chip run.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.lenet5 import LeNetConfig
from repro.core.lenet import init_lenet_params, lenet_bits, make_lenet_train_step
from repro.kernels import decode_prologue as DP
from repro.kernels import flash_attention as FA
from repro.kernels import ops as kops
from repro.kernels import paged_attention as PA
from repro.kernels.bp_fused_unit import bp_fused_unit
from repro.kernels.bp_gstep import bp_gstep
from repro.kernels.fxp_matmul import fxp_matmul
from repro.kernels.sgd_dw_update import sgd_dw_update

QWEN = get_config("qwen1.5-0.5b")
DANUBE = get_config("h2o-danube-3-4b")
TRAIN_BATCH, TRAIN_SEQ = 8, 1024          # the chip smoke train phase
SERVE_SLOTS = 8                           # the chip smoke serve phase
LENET = LeNetConfig()                     # the paper's own network
LENET_BATCH = 64                          # the chip smoke paper phase


def _dense_shapes(role: str):
    """(m, n, k) grids of the qwen train step's dense units for one role:
    fwd = x @ w (fxp_matmul), dx = dz @ w^T (bp_gstep), dw = x^T @ dz
    (sgd_dw_update)."""
    t = TRAIN_BATCH * TRAIN_SEQ
    out = []
    for (m, n, k, _item) in kops.train_tune_shapes(
            QWEN, TRAIN_BATCH, TRAIN_SEQ)["blocks"]:
        is_dw = k == t
        if (role == "dw") == is_dw and (m, n, k) not in out:
            out.append((m, n, k))
    return out


def _grid_id(mnk):
    return "x".join(map(str, mnk))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back without one:
    keep them out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache, monkeypatch):
    """``compile_tpu(fn, *avals, precision=...)`` -> compiled text; asserts
    the kernel is there.  Code that asks ``kops`` for the platform sees the
    TPU answer (compiled kernels, double-buffered fetch, int8 for "auto").
    Compiles run at the default matmul precision the drivers use (other
    test modules raise the process-wide default to "highest", under which
    Mosaic refuses bf16 matmuls) unless a test asks for another."""
    monkeypatch.setattr(kops, "interpret_mode", lambda kernel: False)
    monkeypatch.setattr(kops, "_on_cpu", lambda: False)

    def run(fn, *avals, precision="default"):
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), avals)
        with jax.default_matmul_precision(precision):
            text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return run


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _payload(datapath):
    return (jnp.int8, 1) if datapath == "int8" else (jnp.float32, 4)


def _scalar():
    return _s((), jnp.float32)


@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("datapath", ["int8", "emulate"])
@pytest.mark.parametrize("mnk", _dense_shapes("fwd"), ids=_grid_id)
def test_fxp_matmul_compiles(compile_tpu, mnk, datapath, double_buffer):
    m, n, k = mnk
    dt, item = _payload(datapath)
    bm, bn, bk = kops.tune_blocks(m, n, k, itemsize=item)
    kw = dict(bm=bm, bn=bn, bk=bk, datapath=datapath,
              double_buffer=double_buffer, out_bits=None)
    if datapath == "int8":
        compile_tpu(lambda x, w, s: fxp_matmul(x, w, scale=s, **kw),
                    _s((m, k), dt), _s((k, n), dt), _scalar())
    else:
        compile_tpu(lambda x, w: fxp_matmul(x, w, xa_bits=(4, 10),
                                            w_bits=(2, 12), **kw),
                    _s((m, k), dt), _s((k, n), dt))


@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("datapath", ["int8", "emulate"])
@pytest.mark.parametrize("mnk", _dense_shapes("fwd"), ids=_grid_id)
def test_bp_gstep_compiles(compile_tpu, mnk, datapath, double_buffer):
    t, din, dout = mnk
    dt, item = _payload(datapath)
    bm, bn, bk = kops.tune_blocks(t, din, dout, itemsize=item)
    kw = dict(bm=bm, bn=bn, bk=bk, datapath=datapath,
              double_buffer=double_buffer)
    g, w, z = _s((t, dout), dt), _s((din, dout), dt), _s((t, din), jnp.float32)
    if datapath == "int8":
        # the dense unit's dx leg: no derivative input, no re-quantization
        compile_tpu(lambda g, w, s: bp_gstep(g, w, None, g_bits=None,
                                             act="identity", scale=s, **kw),
                    g, w, _scalar())
    else:
        compile_tpu(lambda g, w, z: bp_gstep(g, w, z, g_bits=(2, 12),
                                             act="relu", **kw), g, w, z)


@pytest.mark.parametrize("datapath", ["int8", "emulate"])
@pytest.mark.parametrize("mnk", _dense_shapes("dw"), ids=_grid_id)
def test_sgd_dw_update_compiles(compile_tpu, mnk, datapath):
    din, dout, t = mnk
    dt, item = _payload(datapath)
    bm, bn, bk = kops.tune_blocks(din, dout, t, itemsize=item)
    kw = dict(bm=bm, bn=bn, bk=bk, datapath=datapath)
    x, g = _s((t, din), dt), _s((t, dout), dt)
    w = _s((din, dout), jnp.float32)
    if datapath == "int8":
        compile_tpu(lambda x, g, w, s, lr: sgd_dw_update(
            x, g, w, lr, scale=s, **kw), x, g, w, _scalar(), _scalar())
        compile_tpu(lambda x, g, s: sgd_dw_update(x, g, None, 0.0, scale=s,
                                                  **kw), x, g, _scalar())
    else:
        compile_tpu(lambda x, g, w, lr: sgd_dw_update(
            x, g, w, lr, w_bits=(2, 12), **kw), x, g, w, _scalar())


@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("datapath", ["int8", "emulate"])
def test_bp_fused_unit_compiles(compile_tpu, datapath, double_buffer):
    t, din, dout = LENET_BATCH, LENET.hidden, LENET.hidden
    dt, item = _payload(datapath)
    bt = kops.tune_fused(t, din, dout, itemsize=item)
    assert bt is not None
    kw = dict(bt=bt, datapath=datapath, double_buffer=double_buffer,
              g_bits=(2, 12), w_bits=(2, 12), act="relu")
    g, x = _s((t, dout), dt), _s((t, din), dt)
    w, z = _s((din, dout), jnp.float32), _s((t, din), jnp.float32)
    if datapath == "int8":
        compile_tpu(lambda g, w, x, z, lr, sg, sx: bp_fused_unit(
            g, w, x, z, lr, g_scale=sg, x_scale=sx, **kw),
            g, w, x, z, _scalar(), _scalar(), _scalar())
    else:
        compile_tpu(lambda g, w, x, z, lr: bp_fused_unit(g, w, x, z, lr, **kw),
                    g, w, x, z, _scalar())


def test_lenet_int8_step_compiles(compile_tpu):
    """The paper phase's whole step: every SGD-unit frame on the int8
    kernels (the 10-class head has no aligned tile and stays on XLA), at
    the "highest" matmul precision its f32 oracle runs under."""
    params = jax.eval_shape(lambda: init_lenet_params(jax.random.key(0), LENET))
    step = make_lenet_train_step(LENET, lenet_bits(LENET.num_layers), "int8")
    text = compile_tpu(lambda p, x, y, lr: step(p, (x, y), lr), params,
                       _s((LENET_BATCH, LENET.input_dim), jnp.float32),
                       _s((LENET_BATCH,), jnp.int32), _scalar(),
                       precision="highest")
    assert text.count("tpu_custom_call") >= LENET.num_layers


@pytest.mark.parametrize("cache", ["bfloat16", "int8"])
def test_paged_attention_compiles(compile_tpu, cache):
    """The serve phase's head geometry and slot count.  Its own pool
    (max_len 1024) is over the VMEM budget and falls back (known); the
    kernel is compiled at the longest sequence the budget admits."""
    hkv, hd, bs = QWEN.num_kv_heads, QWEN.head_dim, 8
    h = QWEN.num_heads
    m = next(m for m in (128, 64, 32, 16, 8)
             if kops.tune_paged(1 + SERVE_SLOTS * m, bs, m, hkv, hd,
                                h // hkv, itemsize=2) is not None)
    n = 1 + SERVE_SLOTS * m
    kv = jnp.int8 if cache == "int8" else jnp.bfloat16
    pool = {"k": _s((n, bs, hkv, hd), kv), "v": _s((n, bs, hkv, hd), kv)}
    if cache == "int8":
        pool.update(k_scale=_s((n, bs), jnp.float32),
                    v_scale=_s((n, bs), jnp.float32))
    names = sorted(pool)
    q = _s((SERVE_SLOTS, h, hd), jnp.bfloat16)
    tables = _s((SERVE_SLOTS, m), jnp.int32)
    lens = _s((SERVE_SLOTS,), jnp.int32)

    def fn(q, tables, lens, *leaves):
        return PA._call_kernel(q, dict(zip(names, leaves)), tables, lens,
                               h // hkv, hd ** -0.5)
    compile_tpu(fn, q, tables, lens, *(pool[k] for k in names))


@pytest.mark.parametrize("kn", [(QWEN.d_model, QWEN.d_ff),
                                (QWEN.d_ff, QWEN.d_model)], ids=_grid_id)
def test_decode_dense_unit_compiles(compile_tpu, kn):
    """The serve phase's decode MLP: one token per slot through the int8
    dense unit."""
    k, n = kn
    compile_tpu(lambda x, w: kops.dense_fwd(x, w, "int8"),
                _s((SERVE_SLOTS, k), jnp.bfloat16), _s((k, n), jnp.float32))


@pytest.mark.parametrize("int8", [False, True])
def test_decode_prologue_compiles(compile_tpu, int8):
    d, h, hkv, hd = (QWEN.d_model, QWEN.num_heads, QWEN.num_kv_heads,
                     QWEN.head_dim)
    assert kops.tune_prologue(d, h, hkv, hd, itemsize=1 if int8 else 2)
    wdt = jnp.int8 if int8 else jnp.float32
    avals = [_s((SERVE_SLOTS, d), jnp.bfloat16), _s((1, d), jnp.float32),
             _s((d, h * hd), wdt), _s((d, hkv * hd), wdt),
             _s((d, hkv * hd), wdt), _s((3,), jnp.float32),
             _s((1, h * hd), jnp.float32), _s((1, hkv * hd), jnp.float32),
             _s((1, hkv * hd), jnp.float32)]

    def fn(x2, ns, wq, wk, wv, wsc, bq, bk, bv):
        return DP._call_kernel(x2, ns, wq, wk, wv, wsc if int8 else None,
                               (bq, bk, bv), int8=int8,
                               eps=float(QWEN.norm_eps))
    compile_tpu(fn, *avals)


def _kernel_call(name):
    """The kernel's int8 call at the first grid of its role in the train
    step, as (fn, *avals)."""
    role = "dw" if name == "sgd_dw_update" else "fwd"
    m, n, k = _dense_shapes(role)[0]
    bm, bn, bk = kops.tune_blocks(m, n, k, itemsize=1)
    kw = dict(bm=bm, bn=bn, bk=bk, datapath="int8")
    s8 = jnp.int8
    if name == "fxp_matmul":
        return (lambda x, w, s: fxp_matmul(x, w, scale=s, out_bits=None,
                                           **kw),
                _s((m, k), s8), _s((k, n), s8), _scalar())
    if name == "bp_gstep":
        return (lambda g, w, s: bp_gstep(g, w, None, g_bits=None,
                                         act="identity", scale=s, **kw),
                _s((m, k), s8), _s((n, k), s8), _scalar())
    return (lambda x, g, s: sgd_dw_update(x, g, None, 0.0, scale=s, **kw),
            _s((k, m), s8), _s((k, n), s8), _scalar())


@pytest.mark.parametrize("name", ["fxp_matmul", "bp_gstep", "sgd_dw_update"])
def test_kernel_carries_its_name(compile_tpu, name):
    """The Mosaic call of each dense-unit kernel is named after the kernel
    in the compiled program (its instruction and its op_name), so that its
    device time can be found by name."""
    fn, *avals = _kernel_call(name)
    text = compile_tpu(fn, *avals)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", ln), ln[:200]
        assert re.search(rf'op_name="[^"]*/{name}/[^"]*"', ln), ln[:200]


# The benchmark's train cells: [B, T] and the model's heads.
FLASH_CELLS = {"qwen": (QWEN, 16, 1024), "danube": (DANUBE, 1, 4096)}


@pytest.mark.parametrize("vjp", [False, True], ids=["fwd", "vjp"])
@pytest.mark.parametrize("cell", list(FLASH_CELLS))
def test_flash_attention_compiles(compile_tpu, cell, vjp):
    """The flash-attention kernels at the train cells' shapes (qwen MHA
    head 64; danube GQA 32/8, head 120 padded to 128), forward alone and
    forward with its vjp, each Mosaic call under its kernel's name."""
    cfg, b, t = FLASH_CELLS[cell]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16

    def attend(q, k, v):
        return FA.flash_attention(q, k, v, scale=hd ** -0.5,
                                  window=cfg.swa_window)

    if vjp:
        def fn(q, k, v, do):
            out, back = jax.vjp(attend, q, k, v)
            return out, back(do)
        names = ["flash_attention", "flash_attention_dq",
                 "flash_attention_dkv"]
        avals = [_s((b, t, h, hd), bf16), _s((b, t, hkv, hd), bf16),
                 _s((b, t, hkv, hd), bf16), _s((b, t, h, hd), bf16)]
    else:
        fn, names = attend, ["flash_attention"]
        avals = [_s((b, t, h, hd), bf16), _s((b, t, hkv, hd), bf16),
                 _s((b, t, hkv, hd), bf16)]
    text = compile_tpu(fn, *avals)
    calls = sorted(re.match(r"\s*(?:ROOT )?%([\w.]+) = ", ln).group(1)
                   for ln in text.splitlines()
                   if "tpu_custom_call" in ln and " custom-call(" in ln)
    assert [c.split(".")[0] for c in calls] == sorted(names), calls
