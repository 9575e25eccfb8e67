"""PartitionSpec assignment for parameters, optimizer state, batches, and
decode state.

Policy (megatron-style 2D: data axes x "model"):

  * embedding [V, D]          -> vocab-sharded over "model" (the CE head is
                                 vocab-parallel; the embed lookup psums)
  * attention q/k/v [D, H, h] -> head-sharded over "model"
  * attention out  [H, h, D]  -> head-sharded (row-parallel: one psum/block)
  * MLP up/gate [D, F]        -> column-parallel; down [F, D] row-parallel
  * MoE expert stacks [E,D,F] -> expert-parallel when E divides the model
                                 axis, else F-sharded (TP inside the expert)
  * vectors / norms / biases  -> replicated
  * anything unrecognized     -> replicated (always correct, never wrong)

Every rule is divisibility-guarded: a dim that doesn't divide the axis size
falls back to replicated instead of uneven sharding, so the same code
serves the 2-device test meshes and the 512-chip production mesh.

Stacked (scanned) parameters carry a leading layer axis; rules address
dims from the END so they apply to both stacked and unstacked leaves.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _is_pspec(x) -> bool:
    return isinstance(x, P)


def to_named(pspecs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=_is_pspec)


def replicated(specs, mesh):
    """Fully-replicated NamedSharding tree matching ``specs``' structure."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), specs)


def _model_size(mesh) -> int:
    return dict(mesh.shape).get("model", 1)


def _batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axes_size(mesh, axes) -> int:
    shape = dict(mesh.shape)
    n = 1
    for a in axes:
        n *= shape[a]
    return n


def _spec(ndim: int, dim_from_end: int, axis: str) -> P:
    """P with ``axis`` at position ndim-dim_from_end, None elsewhere."""
    entries = [None] * ndim
    entries[ndim - dim_from_end] = axis
    return P(*entries)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _param_spec(path_names, leaf_name: str, shape, m: int) -> P:
    nd = len(shape)

    def ok(dim_from_end: int) -> bool:
        return nd >= dim_from_end and shape[nd - dim_from_end] % m == 0

    if m <= 1 or nd == 0:
        return P()

    in_moe = "moe" in path_names and "shared" not in path_names

    if leaf_name == "embed" and nd == 2:
        return _spec(nd, 2, "model") if ok(2) else P()
    if leaf_name == "lm_head" and nd == 2:
        return _spec(nd, 1, "model") if ok(1) else P()

    if leaf_name in ("wq", "wk", "wv") and nd >= 3:
        return _spec(nd, 2, "model") if ok(2) else P()     # [.., D, H, hd]
    if leaf_name in ("bq", "bk", "bv") and nd >= 2:
        return _spec(nd, 2, "model") if ok(2) else P()     # [.., H, hd]
    if leaf_name == "wo" and nd >= 3:
        return _spec(nd, 3, "model") if ok(3) else P()     # [.., H, hd, D]

    # MLA projections
    if leaf_name in ("w_uk", "w_uv") and nd >= 3:
        return _spec(nd, 2, "model") if ok(2) else P()     # [.., r, H, hd]

    if in_moe:
        if leaf_name in ("w_gate", "w_up") and nd >= 3:    # [.., E, D, F]
            if ok(3):
                return _spec(nd, 3, "model")
            return _spec(nd, 1, "model") if ok(1) else P()
        if leaf_name == "w_down" and nd >= 3:              # [.., E, F, D]
            if ok(3):
                return _spec(nd, 3, "model")
            return _spec(nd, 2, "model") if ok(2) else P()
        if leaf_name == "router":
            return P()
    else:
        if leaf_name in ("w_gate", "w_up") and nd >= 2:    # [.., D, F]
            return _spec(nd, 1, "model") if ok(1) else P()
        if leaf_name == "w_down" and nd >= 2:              # [.., F, D]
            return _spec(nd, 2, "model") if ok(2) else P()

    # Mamba projections: shard the d_inner columns (see ssm.init_mamba)
    if leaf_name in ("w_z", "w_x") and nd >= 2:
        return _spec(nd, 1, "model") if ok(1) else P()
    if leaf_name == "out_proj" and nd >= 2:
        return _spec(nd, 2, "model") if ok(2) else P()

    return P()


def param_pspecs(cfg: ModelConfig, params, mesh):
    """PartitionSpec tree mirroring ``params`` (arrays or ShapeDtypeStructs)."""
    m = _model_size(mesh)

    def spec(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "name", k)))
                 for k in path]
        return _param_spec(names, names[-1] if names else "", leaf.shape, m)

    return jax.tree_util.tree_map_with_path(spec, params)


# ---------------------------------------------------------------------------
# Optimizer state
# ---------------------------------------------------------------------------

def opt_pspecs(cfg: ModelConfig, opt_specs, p_pspecs, mesh):
    """Specs for the train state: moment buffers inherit their parameter's
    spec; ``m_s`` (rowwise int8-momentum scales) drops the last dim."""
    def drop_last(s: P) -> P:
        return P(*tuple(s)[:-1]) if len(tuple(s)) else P()

    out = {}
    for key, state in opt_specs.items():
        pspec = p_pspecs[key]
        fields = {}
        for fname, sub in state.items():
            if fname == "m_s":
                fields[fname] = jax.tree.map(drop_last, pspec,
                                             is_leaf=_is_pspec)
            else:
                fields[fname] = pspec
        out[key] = fields
    return out


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------

def batch_pspecs(specs, mesh):
    """Shard dim 0 of every batch leaf over the data axes (divisibility-
    guarded); scalars and non-divisible leaves replicate."""
    baxes = _batch_axes(mesh)
    n = _axes_size(mesh, baxes)

    def spec(leaf):
        shape = leaf.shape
        if not baxes or not shape or shape[0] % n != 0:
            return P()
        entry = baxes[0] if len(baxes) == 1 else baxes
        return P(entry, *([None] * (len(shape) - 1)))

    return jax.tree.map(spec, specs)


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def decode_state_pspecs(cfg: ModelConfig, state_specs, mesh):
    """Serving-state specs: caches shard their batch dim over the data axes.

    Cache layouts (see serving/engine.py): plain families stack per-layer
    caches as [L, B, ...]; hybrid attention caches are [G, B, ...] and
    hybrid mamba caches [G, K, B, ...].  ``pos`` is a replicated scalar.
    """
    baxes = _batch_axes(mesh)
    n = _axes_size(mesh, baxes)
    entry = None if not baxes else (baxes[0] if len(baxes) == 1 else baxes)

    def spec(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "name", k)))
                 for k in path]
        shape = leaf.shape
        if entry is None or "pos" in names or len(shape) < 2:
            return P()
        bdim = 2 if "mamba" in names else 1
        if len(shape) <= bdim or shape[bdim] % n != 0:
            return P()
        entries = [None] * len(shape)
        entries[bdim] = entry
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, state_specs)
