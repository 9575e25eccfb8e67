"""Train -> serve export: turn a searched ``BitPlan`` into the serving
engine's int8 configuration, with a provable numerics contract.

The contract has three parts, each checked bit-for-bit by
``verify_train_serve_parity`` (and drilled in tests/test_bit_search.py):

1. **Grid embedding** — a train-time (I,F) format with bitwidth <= 8
   embeds into int8 *exactly*: payload is the fixed-point integer ``k``,
   scale is ``2^-F``, so ``dequantize(quantize_int8_fxp(x_q)) == x_q``
   for any ``x_q`` already on the (I,F) grid.  Wider formats keep their
   8 MSBs: the serve-side value equals train-time quantization at the
   effective format ``(I, F - shift)`` — the precision loss is exactly
   "drop ``shift`` low fractional bits", nothing else.
2. **KV cache** — the per-token absmax rule used by the paged int8 pool
   (``serving.engine.quant_kv_rows``) is restated here
   (``kv_reference``) and held bitwise equal, so the exported config
   documents precisely what the serving cache stores.
3. **Decode prologue** — the fused int8 decode prologue consumes
   weights quantized by the rule exported here
   (``export_prologue_weights``): ``decode_prologue`` under the int8
   backend is bitwise equal to the reference path fed those exported
   payloads.

Everything downstream of a ``ServeQuantPlan`` is therefore explainable
in train-time terms: no hidden requantization between the two stacks.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.quant.fixed_point import quantize
from repro.quant.int8 import (dequantize_int8, int8_spec,
                              quantize_int8_absmax, quantize_int8_fxp,
                              transport_bits)
from repro.search.plan import BitPlan

SERVE_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class LayerQuant:
    """One layer's serve-side quantization: either the exact (I,F) grid
    ("fxp", bitwidth <= 8) or dynamic per-tensor absmax ("absmax")."""

    layer: int
    i_bits: int
    f_bits: int
    mode: str          # "fxp" | "absmax"
    scale: float       # int8 scale for fxp mode (2^(shift-F))
    qmin: int
    qmax: int
    shift: int         # dropped low fractional bits (0 = exact embedding)

    @property
    def exact(self) -> bool:
        return self.shift == 0

    @property
    def eff_f_bits(self) -> int:
        """Fractional bits that survive the int8 embedding."""
        return self.f_bits - self.shift


@dataclasses.dataclass(frozen=True)
class ServeQuantPlan:
    """The serving-side rendering of a trained ``BitPlan``."""

    layers: Tuple[LayerQuant, ...]
    cache_dtype: str = "int8"      # ServeConfig.cache_dtype
    kernel_backend: str = "int8"   # kernel datapath for the prologue

    def serve_config_kwargs(self) -> dict:
        """kwargs to splat into ``serving.ServeConfig``."""
        return {"cache_dtype": jnp.int8}

    def to_json(self) -> dict:
        return {
            "schema": SERVE_SCHEMA,
            "cache_dtype": self.cache_dtype,
            "kernel_backend": self.kernel_backend,
            "kv_rule": "per-token absmax: scale=max(|row|,1e-8)/127, "
                       "payload=clip(round(x/scale),-127,127)",
            "layers": [dataclasses.asdict(lq) for lq in self.layers],
        }

    @classmethod
    def from_json(cls, obj: dict) -> "ServeQuantPlan":
        if obj.get("schema", 1) != SERVE_SCHEMA:
            raise ValueError(f"unknown ServeQuantPlan schema {obj.get('schema')}")
        layers = tuple(
            LayerQuant(layer=int(l["layer"]), i_bits=int(l["i_bits"]),
                       f_bits=int(l["f_bits"]), mode=str(l["mode"]),
                       scale=float(l["scale"]), qmin=int(l["qmin"]),
                       qmax=int(l["qmax"]), shift=int(l["shift"]))
            for l in obj["layers"])
        return cls(layers=layers, cache_dtype=str(obj["cache_dtype"]),
                   kernel_backend=str(obj["kernel_backend"]))


def to_serve_plan(plan: BitPlan) -> ServeQuantPlan:
    """Render each layer's trained (I,F) format as its int8 serving rule."""
    layers = []
    for idx, (i_b, f_b) in enumerate(plan.formats()):
        if i_b > 7:
            raise ValueError(
                f"layer {idx} format ({i_b},{f_b}): I > 7 cannot keep its "
                f"MSBs in int8 (effective F would be negative)")
        spec = int8_spec(i_b, f_b)
        mode = "fxp" if transport_bits((i_b, f_b)) is not None else "absmax"
        layers.append(LayerQuant(
            layer=idx, i_bits=i_b, f_bits=f_b, mode=mode, scale=spec.scale,
            qmin=spec.qmin, qmax=spec.qmax, shift=spec.shift))
    return ServeQuantPlan(layers=tuple(layers))


def save_serve_plan(sp: ServeQuantPlan, path: str) -> None:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(sp.to_json(), f, indent=2, sort_keys=True)
        f.write("\n")


def load_serve_plan(path: str) -> ServeQuantPlan:
    with open(path) as f:
        return ServeQuantPlan.from_json(json.load(f))


# ---------------------------------------------------------------------------
# The exported numerics rules (restated independently of the engine)
# ---------------------------------------------------------------------------

def kv_reference(x):
    """The exported KV-cache rule — must stay bitwise equal to
    ``serving.engine.quant_kv_rows`` (enforced by the conformance suite)."""
    xf = jnp.asarray(x).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(1, 2))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[:, None, None]), -127, 127)
    return q.astype(jnp.int8), scale


def export_prologue_weights(attn_params: dict):
    """The exported decode-prologue weight rule: per-tensor absmax int8 on
    the 2D-reshaped QKV projections, scales stacked [3] — exactly what
    ``kernels.decode_prologue`` computes internally under the int8 backend.

    Returns ``(qwq, qwk, qwv, wscales)`` ready for ``DP._ref_int8``.
    """
    wq, wk, wv = attn_params["wq"], attn_params["wk"], attn_params["wv"]
    d, h, hd = wq.shape
    hkv = wk.shape[1]
    qwq, swq = quantize_int8_absmax(wq.reshape(d, h * hd))
    qwk, swk = quantize_int8_absmax(wk.reshape(d, hkv * hd))
    qwv, swv = quantize_int8_absmax(wv.reshape(d, hkv * hd))
    return qwq, qwk, qwv, jnp.stack([swq, swk, swv])


def serve_layer_quant(x, lq: LayerQuant):
    """Apply one exported layer rule to a tensor: (payload, scale)."""
    if lq.mode == "fxp":
        return quantize_int8_fxp(x, lq.i_bits, lq.f_bits)
    return quantize_int8_absmax(x)


# ---------------------------------------------------------------------------
# The conformance checks
# ---------------------------------------------------------------------------

def check_grid_embedding(plan: BitPlan, key=None) -> dict:
    """Part 1 of the contract, per layer of the plan.

    For tensors already on the train-time (I,F) grid, the serve-side
    dequantized value must equal train-time quantization at the effective
    format (I, F - shift) bitwise — and the tensor itself when the format
    embeds exactly (bitwidth <= 8).
    """
    key = key if key is not None else jax.random.key(0)
    max_diff_msb = 0.0
    max_diff_exact = 0.0
    for idx, (i_b, f_b) in enumerate(plan.formats()):
        spec = int8_spec(i_b, f_b)
        k = jax.random.fold_in(key, idx)
        # span the representable range including saturation edges
        x = jax.random.uniform(k, (512,), jnp.float32,
                               -1.5 * 2.0 ** i_b, 1.5 * 2.0 ** i_b)
        x_q = quantize(x, i_b, f_b)
        payload, scale = quantize_int8_fxp(x_q, i_b, f_b)
        deq = dequantize_int8(payload, scale)
        want = quantize(x_q, i_b, f_b - spec.shift)
        max_diff_msb = max(max_diff_msb,
                           float(jnp.max(jnp.abs(deq - want))))
        if spec.exact:
            max_diff_exact = max(max_diff_exact,
                                 float(jnp.max(jnp.abs(deq - x_q))))
    return {"grid_msb_max_diff": max_diff_msb,
            "grid_exact_max_diff": max_diff_exact,
            "ok": max_diff_msb == 0.0 and max_diff_exact == 0.0}


def check_kv_parity(key=None, rows: int = 64, heads: int = 4,
                    head_dim: int = 16) -> dict:
    """Part 2: exported KV rule == the engine's, payloads and scales."""
    from repro.serving import engine

    key = key if key is not None else jax.random.key(1)
    x = 3.0 * jax.random.normal(key, (rows, heads, head_dim), jnp.float32)
    q_eng, s_eng = engine.quant_kv_rows(x)
    q_exp, s_exp = kv_reference(x)
    payload_diff = int(jnp.max(jnp.abs(
        q_eng.astype(jnp.int32) - q_exp.astype(jnp.int32))))
    scale_diff = float(jnp.max(jnp.abs(s_eng - s_exp)))
    return {"kv_payload_max_diff": payload_diff,
            "kv_scale_max_diff": scale_diff,
            "ok": payload_diff == 0 and scale_diff == 0.0}


def check_prologue_parity(key=None) -> dict:
    """Part 3: ``decode_prologue`` under the int8 backend == the reference
    int8 path fed weights quantized by the exported rule, bitwise."""
    from repro.kernels import decode_prologue as DP
    from repro.kernels import ops as kops
    from repro.models.config import ModelConfig

    key = key if key is not None else jax.random.key(2)
    cfg = ModelConfig(name="bit-export-parity", family="dense", num_layers=1,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=64,
                      vocab_size=64, compute_dtype="float32")
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    norm = {"scale": 1.0 + 0.1 * jax.random.normal(ks[0], (d,), jnp.float32)}
    attn = {"wq": jax.random.normal(ks[1], (d, h, hd), jnp.float32) * 0.1,
            "wk": jax.random.normal(ks[2], (d, hkv, hd), jnp.float32) * 0.1,
            "wv": jax.random.normal(ks[3], (d, hkv, hd), jnp.float32) * 0.1}
    x = jax.random.normal(ks[4], (3, 1, d), jnp.float32)
    pos = jnp.array([0, 5, 17], jnp.int32)

    qwq, qwk, qwv, wscales = export_prologue_weights(attn)
    ref = jax.jit(lambda xx: DP.rows_to_heads(*DP._ref_int8(
        xx[:, 0, :], norm["scale"].reshape(1, d), qwq, qwk, qwv, wscales,
        None, eps=float(cfg.norm_eps)), cfg, pos))
    want = ref(x)

    with kops.kernel_backend_ctx("int8"):
        got = jax.jit(
            lambda xx: DP.decode_prologue(norm, attn, xx, cfg, pos))(x)

    diffs = [float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want)]
    return {"prologue_max_diff": max(diffs), "ok": max(diffs) == 0.0}


def verify_train_serve_parity(plan: BitPlan, key=None) -> dict:
    """Run all three conformance checks; ``result['ok']`` is the verdict."""
    key = key if key is not None else jax.random.key(plan.seed)
    out = {}
    out.update(check_grid_embedding(plan, jax.random.fold_in(key, 0)))
    grid_ok = out.pop("ok")
    out.update(check_kv_parity(jax.random.fold_in(key, 1)))
    kv_ok = out.pop("ok")
    out.update(check_prologue_parity(jax.random.fold_in(key, 2)))
    prologue_ok = out.pop("ok")
    out["grid_ok"] = grid_ok
    out["kv_ok"] = kv_ok
    out["prologue_ok"] = prologue_ok
    out["ok"] = grid_ok and kv_ok and prologue_ok
    return out


def assert_parity(plan: BitPlan, key=None) -> dict:
    res = verify_train_serve_parity(plan, key)
    if not res["ok"]:
        raise AssertionError(f"train<->serve parity violated: {res}")
    return res
