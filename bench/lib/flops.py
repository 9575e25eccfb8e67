"""Model FLOPs and dense-unit least times, from shapes alone.

The model dict is a configuration file's ``model`` block (``ModelConfig``
field names).  Model FLOPs count the work the model requires, whatever
implements it: 2 per multiply-add of every weight matmul (the non-embedding
weights plus the V x D head), plus attention's two score/value matmuls over
the keys the causal (and window) mask admits.  A trained token costs three
forward passes' worth; recomputation is not counted.
"""
from __future__ import annotations


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


def keys_attended(start: int, count: int, window=None) -> int:
    """Sum over query positions start..start+count-1 of the keys the causal
    mask (and the sliding window, if any) admits."""
    total = 0
    for p in range(start, start + count):
        total += p + 1 if window is None else min(p + 1, window)
    return total


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


def least_time_s(grids, ops_per_s: float, bytes_per_s: float) -> float:
    """Sum over grids of max(2MNK / peak ops, bytes / peak bandwidth), the
    bytes being int8 operands in and an f32 result out."""
    total = 0.0
    for mm, nn, kk in grids:
        total += max(2.0 * mm * nn * kk / ops_per_s,
                     (mm * kk + kk * nn + 4.0 * mm * nn) / bytes_per_s)
    return total
