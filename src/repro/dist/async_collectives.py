"""Bucketed async ring all-reduce: chunked ``lax.ppermute`` + start/wait.

The blocking collectives in ``dist.collectives`` issue one monolithic op per
tensor; XLA is free to overlap it with independent compute, but the backward
scan gives it nothing independent to overlap WITH — the scan body consumes
the reduced dW immediately.  This module supplies the two pieces the
communication-overlapped backward scan (``core.taxonn.backward_stack`` with
``QuantPolicy.overlap="on"``) is built from:

  * a **ring all-reduce** decomposed into chunked ``lax.ppermute`` steps
    (CATERPILLAR's interleaved ring reduction, Li & Pedram 2017): the tensor
    is split into the ring's g segments and, optionally, ``num_buckets``
    independent bucket streams, so each hop moves a small chunk the
    scheduler can interleave with MXU work instead of one long transfer;

  * an **AsyncHandle start/wait API** that splits the ring at its natural
    seam so the two halves can live in *different* scan iterations:

        handle = all_reduce_start(dW_i, axes)     # scan step i
        ... next layer's G-step/VJP compute ...   # overlap window
        dW_i   = all_reduce_wait(handle)          # scan step i+1

    ``AsyncHandle`` is a registered pytree, so it rides in the scan carry;
    every array it holds has a static shape, making the carry scan-legal.

Dense split: ``start`` runs the reduce-scatter phase (g-1 chunked hops) and
the carry holds only the 1/g-sized reduced shard; ``wait`` runs the
all-gather phase.  Compressed split (the int8 wire format of
``quant.compression``): ``start`` runs a **decompress-add-recompress
reduce-scatter ring** — each hop moves one 1/g compressed segment, so
per-hop wire bytes drop by (g-1)/g vs circulating the full buffer — and
the carry holds only this device's fully-reduced compressed segment;
``wait`` all-gathers the compressed segments and decompresses.  The
per-element error vs ``collectives.compressed_psum`` is bounded by one
codec half-step per compression event: g initial compressions plus g-2
in-ring recompressions, i.e. ``|err| <= (2g - 2) * max_block_absmax / 254``
(see ``_compressed_reduce_scatter``).

**Transport autotuner** (the ``transport=`` knob): the chunked ppermute
ring is the right transport only when its hops genuinely overlap compute;
measured on emulated host-CPU device groups one fused ``lax.psum`` beats
it by ~4x.  ``decide_transport`` picks ``"ring"`` vs ``"psum"`` vs
``"scatter"`` per bucket size — from a MEASURED micro-benchmark of the
reduce + optimizer-update-tail composite on the live device group when
one can run (cached per (compressed, size-bucket, group) like
``kernels.ops.tune_blocks``; prime eagerly via ``prime_transport_cache``),
falling back to a platform latency model inside a trace.  The
``REPRO_TRANSPORT`` env var forces a decision for reproduction runs, and
``dump_transport_cache`` persists the decisions (CI uploads them as a
debugging artifact).  ``transport="psum"`` issues the blocking collective
at ``start`` (dense: one FUSED psum over the whole tree at the tree API —
one rendezvous per layer instead of one per leaf; compressed: the
all-gather wire format of ``compressed_psum``) and returns an
already-complete handle whose ``wait`` is free — the in-flight value still
rides the scan carry, so the scheduler keeps the cross-iteration window.
``transport="scatter"`` (dense only) is the native reduce-scatter /
all-gather split: ``start`` completes a ``lax.psum_scatter`` and the
handle carries this device's fully reduced 1/g chunk; ``wait`` is a
``lax.all_gather``.  Same wire bytes as the fused psum, but the chunk is
a real shard the caller can run the optimizer update on BEFORE gathering
(``shard_chunk`` / ``reduce_scatter_chunk`` / ``all_gather_chunks``) —
the measured ~1.7x win at dW-leaf sizes that makes ``overlap=on`` beat
the blocking scan on CPU device groups.

Axes semantics match ``collectives.compressed_psum``: ``axes`` must name
mesh axes of an enclosing ``shard_map`` body; empty axes (or a group of
one) degrade to the identity — ``wait(start(x)) == x`` bit-exactly, which
is what makes the overlapped scan a pure *schedule* change on one device.
The ring assumes a single-process device group; spanning a multi-process
axis raises ``NotImplementedError`` up front (use ``transport="psum"``
there until the hops are topology-aware).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.collectives import compressed_psum
from repro.quant.compression import BLOCK, compress_int8, decompress_int8

Array = jax.Array

# Auto-bucketing: one bucket per this many payload bytes (capped) so large
# dW tensors become several independent ring streams whose chunks the
# scheduler can interleave, while small tensors stay single-stream.
BUCKET_BYTES = 1 << 20
MAX_BUCKETS = 4

TRANSPORTS = ("ring", "psum", "scatter")
# model fallback: below this payload a ring is latency-bound on real
# accelerators and the fused psum wins; host-CPU device groups share one
# memory system, so the model never picks the ring there
RING_MIN_BYTES = 1 << 20


def _transports_for(compressed: bool) -> Tuple[str, ...]:
    """The compressed wire format has no reduce-scatter split (the int8
    codec blocks straddle the 1/g segment boundary), so ``scatter`` is a
    dense-only transport."""
    return ("ring", "psum") if compressed else TRANSPORTS


def group_size(axes: Iterable[str], num_replicas: Optional[int] = None) -> int:
    """Resolve the reduction-group size for named mesh axes.

    ``num_replicas`` overrides (callers inside a ``shard_map`` body know
    their mesh); otherwise the ambient (abstract) mesh is consulted.
    """
    axes = tuple(axes)
    if num_replicas is not None:
        return int(num_replicas)
    if not axes:
        return 1
    mesh = jax.sharding.get_abstract_mesh()
    shape = dict(getattr(mesh, "shape", {}) or {})
    n = 1
    for a in axes:
        if a not in shape:
            raise ValueError(
                f"cannot resolve ring-group size: axis {a!r} not in the "
                f"ambient mesh {tuple(shape)}; pass num_replicas= explicitly")
        n *= shape[a]
    return n


def _num_buckets(nbytes: int, num_buckets: Optional[int]) -> int:
    if num_buckets is not None:
        return max(1, int(num_buckets))
    return max(1, min(MAX_BUCKETS, nbytes // BUCKET_BYTES))


# ---------------------------------------------------------------------------
# transport autotuner: ring vs psum, per payload-size bucket
# ---------------------------------------------------------------------------

# (compressed, size_bucket_bytes, g) -> {"transport", "source", "us"}
_TRANSPORT_CACHE: dict = {}


def _size_bucket(nbytes: int) -> int:
    """Round the payload up to a power of two so near-identical tensors
    share one measured decision (the tune_blocks per-shape cache idiom,
    coarsened: transport crossover moves in decades, not elements)."""
    b = 1 << 12
    while b < nbytes:
        b <<= 1
    return b


def _forced_transport() -> Optional[str]:
    forced = os.environ.get("REPRO_TRANSPORT", "").strip().lower()
    if forced in TRANSPORTS:
        return forced
    if forced and forced != "auto":
        raise ValueError(
            f"REPRO_TRANSPORT={forced!r} not in {TRANSPORTS + ('auto',)}")
    return None


def _model_transport(nbytes: int, g: int, compressed: bool = False) -> str:
    """Deterministic fallback when no measurement can run (inside a trace,
    or the process doesn't own g devices).  Host-CPU 'devices' share one
    memory system — the emulated ring has nothing to overlap into and
    loses at every size (measured ~4x at 4MB) — so the model only picks
    the ring on a real accelerator backend, and only once the payload is
    big enough to amortize the per-hop latency.  Dense payloads on the
    CPU backend get ``scatter``: the native reduce-scatter + all-gather
    moves the same bytes as one fused psum but hands the caller a 1/g
    shard to run the optimizer update on (measured ~1.7x faster than
    psum + full-tensor update at dW-leaf sizes; callers that cannot
    exploit the shard degrade it to psum)."""
    if jax.default_backend() == "cpu":
        return "psum" if compressed else "scatter"
    return "ring" if nbytes >= RING_MIN_BYTES else "psum"


def _trace_clean() -> bool:
    fn = getattr(jax.core, "trace_state_clean", None)
    try:
        return bool(fn()) if fn is not None else False
    except Exception:
        return False


def _measure_transport(nbytes: int, g: int, compressed: bool,
                       reps: int = 3) -> dict:
    """Time each transport's REDUCE + UPDATE-TAIL composite for one
    bucket-sized payload on a live g-device mesh (eager: never called
    inside a trace).

    What the backward scan actually instantiates per dW leaf is not the
    all-reduce alone but reduce -> optimizer saxpy -> updated params
    available on every device, and the transports differ in where the
    saxpy runs: ``psum``/``ring`` update the full tensor on every device,
    ``scatter`` updates only this device's 1/g shard and all-gathers the
    result (same wire bytes, 1/g the update traffic) — so that composite
    is what gets timed and ranked."""
    n = max(BLOCK * g, (nbytes // 4 // (BLOCK * g)) * BLOCK * g)
    x = jnp.arange(n, dtype=jnp.float32) / n
    mesh = jax.make_mesh((g,), ("_tt",), devices=jax.devices()[:g])
    from jax.sharding import PartitionSpec as P

    def build(transport):
        if transport == "scatter":
            def f(v):
                shard = reduce_scatter_chunk(v, "_tt", g)
                own = shard_chunk(v, "_tt", g)
                new = own - jnp.float32(0.01) * shard
                return all_gather_chunks(new, "_tt", g, v.shape, v.dtype)
        else:
            def f(v):
                dw = ring_all_reduce(v, ("_tt",), num_replicas=g,
                                     compressed=compressed,
                                     transport=transport)
                return v - jnp.float32(0.01) * dw
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False))

    out = {}
    for transport in _transports_for(compressed):
        fn = build(transport)
        jax.block_until_ready(fn(x))            # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            # block EVERY rep: concurrent in-flight executions of one
            # collective module interleave their participants across
            # rendezvous on the CPU backend and deadlock the device group
            jax.block_until_ready(fn(x))
        out[transport] = (time.perf_counter() - t0) / reps * 1e6
    return out


def decide_transport(nbytes: int, g: int, *, compressed: bool = False,
                     allow_measure: bool = True) -> str:
    """Pick the transport for one payload: forced (``REPRO_TRANSPORT``) >
    cached > measured (when a g-device micro-bench can run right now) >
    platform model.  Decisions are cached per (compressed, size-bucket, g)
    so every scan iteration — and every later step build — reuses one
    choice; ``prime_transport_cache`` measures eagerly up front."""
    forced = _forced_transport()
    if forced is not None:
        # the compressed wire format has no scatter split
        return "psum" if (compressed and forced == "scatter") else forced
    if g <= 1:
        return "psum"                     # nothing moves; skip ring setup
    key = (bool(compressed), _size_bucket(nbytes), int(g))
    hit = _TRANSPORT_CACHE.get(key)
    if hit is not None:
        return hit["transport"]
    if allow_measure and g <= len(jax.devices()) and _trace_clean():
        try:
            us = _measure_transport(key[1], g, compressed)
            pick = min(us, key=us.get)
            _TRANSPORT_CACHE[key] = {"transport": pick, "source": "measured",
                                     "us": us}
            return pick
        except Exception:
            pass                          # fall through to the model
    pick = _model_transport(nbytes, g, compressed)
    _TRANSPORT_CACHE[key] = {"transport": pick, "source": "model", "us": {}}
    return pick


def prime_transport_cache(sizes_bytes: Iterable[int], g: int, *,
                          compressed: bool = False) -> dict:
    """Eagerly measure + cache the transport decisions a run will need
    (call BEFORE tracing the step: inside a trace the autotuner can only
    consult the cache or the model).  Returns {bucket_bytes: transport}."""
    out = {}
    for nbytes in sorted({_size_bucket(int(b)) for b in sizes_bytes}):
        out[nbytes] = decide_transport(nbytes, g, compressed=compressed)
    return out


def transport_cache_snapshot() -> dict:
    """Copy of the decision cache, JSON-friendly keys."""
    return {f"compressed={k[0]},bytes={k[1]},g={k[2]}": dict(v)
            for k, v in sorted(_TRANSPORT_CACHE.items())}


def dump_transport_cache(path: str) -> None:
    """Persist the decision cache (the CI bench uploads it for debugging)."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(transport_cache_snapshot(), f, indent=2, sort_keys=True)


def load_transport_cache(snapshot: dict, *, overwrite: bool = False) -> int:
    """Inverse of ``transport_cache_snapshot``: install persisted decisions
    (e.g. the ones a checkpoint carried in its ``extra``) so a RESUMED run
    reuses the original run's measured transports instead of re-measuring —
    which keeps the restarted backward scan's collective schedule, and
    therefore its numerics, identical to the killed run's.  Returns the
    number of entries installed; malformed entries are skipped."""
    n = 0
    for key, entry in (snapshot or {}).items():
        try:
            parts = dict(p.split("=", 1) for p in key.split(","))
            k = (parts["compressed"] == "True", int(parts["bytes"]),
                 int(parts["g"]))
            transport = entry["transport"]
        except (KeyError, ValueError, AttributeError, TypeError):
            continue
        if transport not in TRANSPORTS:
            continue
        if not overwrite and k in _TRANSPORT_CACHE:
            continue
        _TRANSPORT_CACHE[k] = {"transport": transport,
                               "source": f"restored:{entry.get('source', '?')}",
                               "us": dict(entry.get("us") or {})}
        n += 1
    return n


def clear_transport_cache() -> None:
    _TRANSPORT_CACHE.clear()


def _ring_perm(g: int) -> Tuple[Tuple[int, int], ...]:
    return tuple((i, (i + 1) % g) for i in range(g))


def _seg(chunks: Array, i) -> Array:
    """chunks[i % g] with a traced index."""
    g = chunks.shape[0]
    return lax.dynamic_index_in_dim(chunks, jnp.mod(i, g), 0, keepdims=False)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AsyncHandle:
    """An in-flight all-reduce.  Pytree (scan-carry safe): ``arrays`` are
    the in-flight chunks, everything else is static metadata."""

    arrays: Tuple[Array, ...]
    kind: str                      # "identity" | "dense" | "compressed"
    axis: Optional[str]
    g: int
    shape: Tuple[int, ...]
    dtype: object
    n_buckets: int

    def tree_flatten(self):
        return (tuple(self.arrays),
                (self.kind, self.axis, self.g, self.shape, self.dtype,
                 self.n_buckets))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)


def _to_chunks(x: Array, g: int, n_buckets: int) -> Array:
    """[...] -> [n_buckets, g, c] zero-padded chunk view (f32)."""
    flat = x.astype(jnp.float32).reshape(-1)
    c = -(-flat.size // (g * n_buckets))
    flat = jnp.pad(flat, (0, g * n_buckets * c - flat.size))
    # bucket-major so each bucket holds a contiguous [g, c] ring layout
    return flat.reshape(n_buckets, g, c)


def _from_chunks(chunks: Array, shape, dtype) -> Array:
    n = 1
    for d in shape:
        n *= d
    return chunks.reshape(-1)[:n].reshape(shape).astype(dtype)


def _require_single_process() -> None:
    if jax.process_count() > 1:
        raise NotImplementedError(
            "the ppermute ring assumes a single-process device group, but "
            "this runtime spans multiple processes; force the fused "
            "collective instead (transport='psum' or REPRO_TRANSPORT=psum) "
            "until the ring hops are topology-aware")


def _resolve_transport(transport: str, nbytes: int, g: int,
                       compressed: bool) -> str:
    """'auto' consults the decision cache/model (and the REPRO_TRANSPORT
    override); an explicit transport= argument wins over everything.
    ``scatter`` degrades to ``psum`` on the compressed path (the codec
    blocks have no 1/g segment split)."""
    if transport == "auto":
        return decide_transport(int(nbytes), g, compressed=compressed,
                                allow_measure=False)
    if transport not in TRANSPORTS:
        raise ValueError(f"transport={transport!r} not in "
                         f"{TRANSPORTS + ('auto',)}")
    return "psum" if (compressed and transport == "scatter") else transport


def _identity_handle(x: Array) -> AsyncHandle:
    return AsyncHandle((x,), "identity", None, 1, tuple(x.shape), x.dtype, 1)


# ---------------------------------------------------------------------------
# scatter transport: native reduce-scatter / all-gather over 1/g chunks
#
# The payload is viewed flat, zero-padded to g equal chunks; device d owns
# chunk d (``lax.psum_scatter`` row order == ``lax.all_gather`` row order ==
# axis index).  The point of the split is that the chunk is a real 1/g
# SHARD the caller can run the optimizer update on before gathering — the
# ZeRO-style sharded update ``core.taxonn`` uses for elementwise
# optimizers — so the per-device update traffic drops by (g-1)/g while the
# wire bytes match one fused psum.
# ---------------------------------------------------------------------------

def _chunk_len(shape, g: int) -> int:
    n = 1
    for d in shape:
        n *= d
    return -(-n // g)


def _flat_padded(x: Array, g: int) -> Array:
    """[...] -> [g, c] zero-padded flat f32 view (pad skipped when the
    size divides evenly — the common dW-leaf case — so XLA sees a pure
    reshape it can fuse instead of a materialized pad copy)."""
    flat = x.astype(jnp.float32).reshape(-1)
    c = _chunk_len(x.shape, g)
    if g * c != flat.size:
        flat = jnp.pad(flat, (0, g * c - flat.size))
    return flat.reshape(g, c)


def shard_chunk(x: Array, axis, g: int) -> Array:
    """This device's [c] chunk of the padded flat view of ``x`` (no
    collective) — the params/opt-state side of a sharded update."""
    return _seg(_flat_padded(x, g), lax.axis_index(axis))


def reduce_scatter_chunk(x: Array, axis, g: int) -> Array:
    """Native reduce-scatter: the fully reduced [c] chunk this device owns
    (f32).  Chunk order matches ``shard_chunk``/``all_gather_chunks``."""
    return lax.psum_scatter(_flat_padded(x, g), axis,
                            scatter_dimension=0, tiled=False)


def all_gather_chunks(chunk: Array, axis, g: int, shape, dtype) -> Array:
    """Inverse of the chunk split: gather every device's [c] chunk and
    restore the original shape/dtype (padding dropped)."""
    full = lax.all_gather(chunk, axis, tiled=True)
    n = 1
    for d in shape:
        n *= d
    return full[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# dense ring: start = reduce-scatter phase, wait = all-gather phase
# ---------------------------------------------------------------------------

def _reduce_scatter(bucket: Array, axis: str, g: int, hop) -> Array:
    """One bucket [g, c] -> this device's reduced shard [c] after g-1 hops."""
    idx = lax.axis_index(axis)
    acc = _seg(bucket, idx)
    for s in range(1, g):
        acc = hop(acc)
        acc = acc + _seg(bucket, idx - s)
    return acc                     # device d owns reduced segment (d+1) % g


def _all_gather_ring(shard: Array, axis: str, g: int) -> Array:
    """Reduced shard [c] (segment (d+1)%g on device d) -> full [g, c]."""
    perm = _ring_perm(g)
    idx = lax.axis_index(axis)
    c = shard.shape[0]
    out = jnp.zeros((g, c), shard.dtype)
    out = lax.dynamic_update_index_in_dim(out, shard, jnp.mod(idx + 1, g), 0)
    cur = shard
    for s in range(1, g):
        cur = lax.ppermute(cur, axis, perm)
        # arrived from device d-s, which owned segment (d-s+1) % g
        out = lax.dynamic_update_index_in_dim(out, cur,
                                              jnp.mod(idx - s + 1, g), 0)
    return out


# ---------------------------------------------------------------------------
# compressed ring: decompress-add-recompress reduce-scatter + all-gather
# ---------------------------------------------------------------------------

def _compressed_reduce_scatter(x: Array, axis, g: int,
                               hop) -> Tuple[Array, Array]:
    """Reduce-scatter ``x`` over the ring in the int8 wire format.

    Each hop moves ONE compressed 1/g segment (payload + block scales) —
    (g-1)/g fewer wire bytes per hop than circulating the whole compressed
    buffer — at the price of a decompress-add-recompress at every hop
    (NeuroTrainer's in-transit reduce).  Error accounting vs
    ``collectives.compressed_psum`` (which compresses each contribution
    exactly once): every compression event adds at most one codec
    half-step ``block_absmax / 254``; a segment's reduction chain here has
    g-1 in-ring compressions plus the final shard compression, and the
    reference path has g of its own, so the divergence is bounded by
    ``(2g - 2) * max_block_absmax / 254`` per element (absmax of the
    largest partial sum).  Returns this device's fully reduced compressed
    segment ``(payload int8[c], scales f32[c/BLOCK])`` — segment
    ``(d+1) % g`` on device d, the dense-ring convention.
    """
    flat = x.astype(jnp.float32).reshape(-1)
    c = -(-flat.size // g)
    c = -(-c // BLOCK) * BLOCK     # whole scale blocks per segment
    flat = jnp.pad(flat, (0, g * c - flat.size))
    chunks = flat.reshape(g, c)
    idx = lax.axis_index(axis)
    acc = _seg(chunks, idx)
    for s in range(1, g):
        payload, scales = compress_int8(acc)
        payload, scales = hop(payload), hop(scales)
        acc = decompress_int8(payload, scales, (c,), jnp.float32)
        acc = acc + _seg(chunks, idx - s)
    return compress_int8(acc)


def _compressed_all_gather(payload: Array, scales: Array, axis, g: int,
                           shape, dtype) -> Array:
    """All-gather the reduced compressed segments and decompress."""
    perm = _ring_perm(g)
    idx = lax.axis_index(axis)
    c = payload.shape[0]
    full_p = jnp.zeros((g, c), payload.dtype)
    full_s = jnp.zeros((g, c // BLOCK), scales.dtype)

    def place(fp, fs, p, sc, seg):
        fp = lax.dynamic_update_index_in_dim(fp, p, seg, 0)
        fs = lax.dynamic_update_index_in_dim(fs, sc, seg, 0)
        return fp, fs

    full_p, full_s = place(full_p, full_s, payload, scales,
                           jnp.mod(idx + 1, g))
    cur_p, cur_s = payload, scales
    for s in range(1, g):
        cur_p = lax.ppermute(cur_p, axis, perm)
        cur_s = lax.ppermute(cur_s, axis, perm)
        # arrived from device d-s, which owned segment (d-s+1) % g
        full_p, full_s = place(full_p, full_s, cur_p, cur_s,
                               jnp.mod(idx - s + 1, g))
    out = decompress_int8(full_p.reshape(-1), full_s.reshape(-1),
                          (g * c,), jnp.float32)
    n = 1
    for d in shape:
        n *= d
    return out[:n].reshape(shape).astype(dtype)


def all_reduce_start(x: Array, axes: Iterable[str] = (), *,
                     compressed: bool = False,
                     num_replicas: Optional[int] = None,
                     num_buckets: Optional[int] = None,
                     dummy: bool = False,
                     transport: str = "auto") -> AsyncHandle:
    """Begin an all-reduce of ``x`` over the named mesh axes.

    Multi-axis groups ring over the combined axes (``lax.ppermute`` accepts
    the axis tuple and flattens it to one logical ring).  Returns a handle
    whose in-flight arrays are what must travel the scan carry.

    With no axes (or a group of one) there is nothing to move: the start
    short-circuits to a no-op identity handle whose ``wait`` returns ``x``
    bit-exactly (the compressed form carries the codec round-trip of ``x``,
    times ``num_replicas`` when an explicit no-mesh override simulates a
    replicated sum, matching ``collectives.compressed_psum``), so the
    overlapped scan stays bit-identical to the blocking one on one device.

    ``transport`` is ``"auto"`` (per-bucket autotuner decision, see
    ``decide_transport``), ``"ring"``, or ``"psum"``; ``"psum"`` issues the
    blocking fused collective at start and returns an already-complete
    handle.  A ring spanning a multi-process runtime raises
    ``NotImplementedError`` up front.

    ``dummy=True`` skips the start-phase hops/collective and returns a
    handle with the array shapes/dtypes a real start would produce — the
    overlapped scan's warm-up carry, built without burning g-1 hops per
    bucket on garbage.  The wait side needs no flag: it runs uniformly
    inside the scan.
    """
    axes = tuple(axes)
    g = group_size(axes, num_replicas)
    if not axes or g == 1:
        if compressed:
            # the blocking wire-format numerics, kept in ONE place
            x = compressed_psum(x, (), num_replicas=num_replicas)
        return _identity_handle(x)
    transport = _resolve_transport(
        transport, x.size * jnp.dtype(x.dtype).itemsize, g, compressed)
    axis = axes if len(axes) > 1 else axes[0]
    if transport == "psum":
        if dummy:
            return _identity_handle(x)
        out = (compressed_psum(x, axes, num_replicas=num_replicas)
               if compressed else lax.psum(x, axes))
        return _identity_handle(out)
    if transport == "scatter":
        # native reduce-scatter at start; the carry holds the 1/g reduced
        # chunk and wait all-gathers it (dummy: slice this device's chunk
        # locally so the warm-up carry has the right shape, no collective)
        chunk = (shard_chunk(x, axis, g) if dummy
                 else reduce_scatter_chunk(x, axis, g))
        return AsyncHandle((chunk,), "scatter", axis, g, tuple(x.shape),
                           x.dtype, 1)
    _require_single_process()
    hop_perm = _ring_perm(g)

    def hop(v):
        return v if dummy else lax.ppermute(v, axis, hop_perm)

    if compressed:
        payload, scales = _compressed_reduce_scatter(x, axis, g, hop)
        return AsyncHandle((payload, scales), "compressed", axis, g,
                           tuple(x.shape), x.dtype, 1)
    n_buckets = _num_buckets(x.size * 4, num_buckets)
    chunks = _to_chunks(x, g, n_buckets)
    shards = tuple(_reduce_scatter(chunks[b], axis, g, hop)
                   for b in range(n_buckets))
    return AsyncHandle(shards, "dense", axis, g, tuple(x.shape), x.dtype,
                       n_buckets)


def all_reduce_wait(handle: AsyncHandle) -> Array:
    """Complete an in-flight all-reduce and return the elementwise sum
    (identical on every ring member)."""
    if handle.kind == "identity":
        return handle.arrays[0]
    if handle.kind == "scatter":
        return all_gather_chunks(handle.arrays[0], handle.axis, handle.g,
                                 handle.shape, handle.dtype)
    if handle.kind == "compressed":
        payload, scales = handle.arrays
        return _compressed_all_gather(payload, scales, handle.axis,
                                      handle.g, handle.shape, handle.dtype)
    assert handle.kind == "dense", handle.kind
    gathered = jnp.stack([_all_gather_ring(s, handle.axis, handle.g)
                          for s in handle.arrays])
    return _from_chunks(gathered, handle.shape, handle.dtype)


def ring_all_reduce(x: Array, axes: Iterable[str] = (), *,
                    compressed: bool = False,
                    num_replicas: Optional[int] = None,
                    num_buckets: Optional[int] = None,
                    transport: str = "ring") -> Array:
    """Blocking convenience wrapper: ``wait(start(x))`` in one call.

    Defaults to ``transport="ring"`` (the wrapper exists to exercise the
    ring; pass ``"auto"`` to go through the autotuner)."""
    return all_reduce_wait(all_reduce_start(
        x, axes, compressed=compressed, num_replicas=num_replicas,
        num_buckets=num_buckets, transport=transport))


# ---------------------------------------------------------------------------
# tree-level API (the backward scan reduces one layer's dW tree per step)
# ---------------------------------------------------------------------------

def _is_handle(x) -> bool:
    return isinstance(x, AsyncHandle)


def resolve_leaf_transports(tree, axes: Iterable[str] = (), *,
                            compressed: bool = False,
                            num_replicas: Optional[int] = None,
                            transport: str = "auto") -> list:
    """The STATIC per-leaf transport decisions ``tree_all_reduce_start``
    would make for ``tree`` (flatten order), resolved from leaf byte sizes
    alone.  Decisions are plain Python strings, so callers can shape their
    program around them at trace time — ``core.taxonn`` uses this to give
    blocking-transport leaves a same-iteration update (and scatter leaves
    a sharded one) while only ring leaves ride the depth pipeline."""
    axes = tuple(axes)
    g = group_size(axes, num_replicas)
    if not axes or g == 1:
        return ["psum" for _ in jax.tree.leaves(tree)]

    def nbytes(x):        # works for arrays and ShapeDtypeStructs alike
        n = 1
        for d in x.shape:
            n *= int(d)
        return n * jnp.dtype(x.dtype).itemsize
    return [_resolve_transport(transport, nbytes(x), g, compressed)
            for x in jax.tree.leaves(tree)]


def tree_all_reduce_start(tree, axes: Iterable[str] = (), *,
                          compressed: bool = False,
                          num_replicas: Optional[int] = None,
                          num_buckets: Optional[int] = None,
                          dummy: bool = False,
                          transport: str = "auto"):
    """Start one all-reduce per leaf; returns a tree of AsyncHandles.

    Dense leaves whose resolved transport is ``"psum"`` are FUSED into one
    variadic ``lax.psum`` over all of them — a single rendezvous per call
    (per layer, in the backward scan) instead of one per leaf; XLA binds a
    pytree psum as one all-reduce op with variadic operands.  Ring leaves
    (and the compressed path, whose wire format is already one buffer per
    leaf) start individually.
    """
    axes = tuple(axes)
    g = group_size(axes, num_replicas)
    if not axes or g == 1 or compressed:
        return jax.tree.map(
            lambda x: all_reduce_start(x, axes, compressed=compressed,
                                       num_replicas=num_replicas,
                                       num_buckets=num_buckets, dummy=dummy,
                                       transport=transport),
            tree)
    leaves, treedef = jax.tree.flatten(tree)
    decisions = [_resolve_transport(
        transport, x.size * jnp.dtype(x.dtype).itemsize, g, False)
        for x in leaves]
    handles: list = [None] * len(leaves)
    fuse = [i for i, d in enumerate(decisions) if d == "psum"]
    if fuse:
        if dummy:
            reduced = tuple(leaves[i] for i in fuse)
        else:
            reduced = lax.psum(tuple(leaves[i] for i in fuse), axes)
        for i, r in zip(fuse, reduced):
            handles[i] = _identity_handle(r)
    for i, d in enumerate(decisions):
        if d in ("ring", "scatter"):
            handles[i] = all_reduce_start(
                leaves[i], axes, compressed=False, num_replicas=num_replicas,
                num_buckets=num_buckets, dummy=dummy, transport=d)
    return jax.tree.unflatten(treedef, handles)


def tree_all_reduce_wait(handles):
    """Wait on a tree of AsyncHandles (as produced by tree_all_reduce_start)."""
    return jax.tree.map(all_reduce_wait, handles, is_leaf=_is_handle)
