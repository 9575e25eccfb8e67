"""Activation-sharding rules, perf options, and the ``constrain`` primitive.

The model code never names mesh axes directly.  It tags intermediate
activations with a *logical layout string* — one lowercase letter per array
dimension:

    b  batch                  (sharded over the data axes: ("pod",) "data")
    t  sequence / tokens      (sharded over "model" only under seq_parallel)
    d  d_model / feature      (replicated: the residual stream is TP-replicated)
    v  vocab                  (sharded over "model": vocab-parallel CE head)
    e  experts                (left to the partitioner; propagates from weights)
    c  expert capacity        (left to the partitioner)

``make_default_rules(batch_axes, seq_parallel=...)`` builds the table
mapping letters to mesh-axis assignments; ``activation_sharding_ctx(rules)``
installs it; ``constrain(x, "btd")`` applies the corresponding sharding
constraint — and is a guaranteed no-op outside a mesh/rules context, so
every pure-CPU unit test runs the exact same model code.

Perf options (``perf_options_ctx`` / ``perf_opt``) are trace-time feature
flags (seq_parallel, moe_rowcombine, ce_bf16, flash_attn, pad_heads) that
change layout/scheduling but never math — see tests/test_perf_options.py.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterable, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Array = jax.Array

# Sentinel for "leave this dimension to the partitioner".
UNCONSTRAINED = P.UNCONSTRAINED

_RULES: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "activation_sharding_rules", default=None)
_PERF: contextvars.ContextVar[frozenset] = contextvars.ContextVar(
    "perf_options", default=frozenset())


# ---------------------------------------------------------------------------
# Perf options
# ---------------------------------------------------------------------------

KNOWN_PERF_OPTS = frozenset({
    "seq_parallel", "pad_heads", "moe_rowcombine", "ce_bf16", "flash_attn",
})


@contextlib.contextmanager
def perf_options_ctx(opts: Iterable[str]):
    """Enable a set of §Perf options for the enclosed trace/compile."""
    opts = frozenset(opts)
    unknown = opts - KNOWN_PERF_OPTS
    if unknown:
        raise ValueError(f"unknown perf options: {sorted(unknown)}")
    token = _PERF.set(_PERF.get() | opts)
    try:
        yield
    finally:
        _PERF.reset(token)


def perf_opt(name: str) -> bool:
    """Is the named perf option active? (checked at trace time)"""
    return name in _PERF.get()


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def make_default_rules(batch_axes: Iterable[str],
                       seq_parallel: bool = False) -> dict:
    """Letter -> mesh-axis assignment table (see module docstring).

    ``batch_axes`` are the data-parallel mesh axes, e.g. ``("data",)`` or
    ``("pod", "data")``; the batch dimension shards over all of them.
    ``seq_parallel`` additionally shards the sequence dimension of the
    residual stream over "model" (Megatron sequence parallelism: the
    norm/residual work is 1/TP per device; the surrounding matmuls
    all-gather it back, which XLA overlaps with compute).
    """
    batch_axes = tuple(batch_axes)
    return {
        "b": batch_axes,
        "t": "model" if seq_parallel else None,
        "d": None,
        "v": "model",
        "e": UNCONSTRAINED,
        "c": UNCONSTRAINED,
        # paged-KV serving pool [L, N_blocks, block, kv_heads, head_dim]
        # tagged "lnshd": the block axis shards over the data axes (each
        # data shard owns a slice of the pool) and KV heads over "model"
        # (classic TP serving); layer / in-block slot / head_dim replicate
        "l": None,
        "n": batch_axes,
        "s": None,
        "h": "model",
    }


@contextlib.contextmanager
def activation_sharding_ctx(rules: Optional[dict]):
    """Install a rules table for ``constrain`` inside the block."""
    token = _RULES.set(rules)
    try:
        yield
    finally:
        _RULES.reset(token)


def current_rules() -> Optional[dict]:
    return _RULES.get()


# ---------------------------------------------------------------------------
# Mesh context
# ---------------------------------------------------------------------------

def current_mesh():
    """The ambient mesh (entered via ``jax.set_mesh``), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or getattr(mesh, "empty", False):
        return None
    if not getattr(mesh, "axis_names", ()):
        return None
    return mesh


def model_axis_size_ctx() -> int:
    """Size of the tensor-parallel "model" axis in the ambient mesh (1 if
    no mesh is set or the mesh has no model axis)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    return dict(mesh.shape).get("model", 1)


# ---------------------------------------------------------------------------
# constrain
# ---------------------------------------------------------------------------

def _axis_size(mesh_shape: dict, entry) -> int:
    if isinstance(entry, str):
        return mesh_shape[entry]
    n = 1
    for a in entry:
        n *= mesh_shape[a]
    return n


# When two letters in one tag claim the same mesh axis (e.g. "btv" under
# seq_parallel: 't' and 'v' both want "model"), the lower number wins and
# the loser replicates.  Vocab beats sequence: the CE head's masked-target
# reduction is collective-free only with V sharded (see lm.ce_from_weight).
_AXIS_PRIORITY = {"b": 0, "n": 0, "v": 1, "h": 1, "e": 2, "c": 2, "d": 3,
                  "t": 4, "l": 5, "s": 5}


def _spec_for(logical: str, ndim: int, rules: dict, mesh,
              shape) -> Optional[P]:
    """Build a PartitionSpec for ``logical`` against the ambient mesh.

    Rank adaptation: when the array has fewer dims than the tag (e.g. a
    [B, V] last-token logits tensor tagged "btv"), the first letter maps to
    dim 0 and the trailing letters to the trailing dims — squeezed middle
    dims drop out.  Axes missing from the mesh, already-used axes, and
    non-divisible dims degrade to None (replicated) rather than erroring,
    so one model codebase runs on any mesh topology.
    """
    if ndim < len(logical):
        logical = logical[0] + logical[len(logical) - (ndim - 1):] \
            if ndim >= 2 else logical[-1]
    elif ndim > len(logical):
        return None  # tag can't describe this array; skip the constraint

    mesh_axes = set(mesh.axis_names)
    mesh_shape = dict(mesh.shape)
    used: set = set()
    entries = [None] * len(logical)
    order = sorted(range(len(logical)),
                   key=lambda i: _AXIS_PRIORITY.get(logical[i], 5))
    for dim in order:
        entry = rules.get(logical[dim], UNCONSTRAINED)
        if entry is UNCONSTRAINED:
            entries[dim] = UNCONSTRAINED
            continue
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if a in mesh_axes and a not in used)
        if not axes:
            continue
        if shape[dim] % _axis_size(mesh_shape, axes) != 0:
            continue  # uneven shard: leave replicated
        used.update(axes)
        entries[dim] = axes[0] if len(axes) == 1 else axes
    return P(*entries)


def constrain(x: Array, logical: str) -> Array:
    """Constrain ``x`` to the sharding the active rules assign to the
    logical layout ``logical``.  No-op outside a mesh + rules context."""
    rules = _RULES.get()
    if rules is None:
        return x
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _spec_for(logical, x.ndim, rules, mesh, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
