"""Pipeline-schedule subsystem: GPipe, 1F1B, and interleaved-1F1B.

Two layers live here:

**Execution** — ``pipeline_apply(stage_params, x, body, mesh, schedule)``
runs M microbatches through S stages as pure differentiable JAX: one
``lax.scan`` over the forward diagonal (T = M + S - 1 ticks) with
predicated writes, so forward values AND gradients (via the scan's
transpose) equal the sequential reference exactly.  The pipeline value
``x`` is a pytree ([M, ...] leaves): side values ride the rotating buffer
with the activation — per-microbatch reduce-class accumulators (aux-loss
statistics a stage adds to) and the microbatch index itself, which stages
use to slice broadcast-class operands (an encoder-output fan-out) down to
their current microbatch.  Warm-up/drain ticks
compute on zero-filled garbage that is never written to the output.  The
schedule selects the *stage placement*: GPipe/1F1B pin stage s to pipe
device s; interleaved-1F1B assigns ``num_virtual`` non-contiguous virtual
stages per device (Megatron-style round-robin, stage s -> device s mod D)
by permuting the rotating buffer's storage order, which changes the
collective-permute pattern the "pipe" mesh axis sees.

**Cost model** — each ``Schedule`` builds a tick table (which (stage,
microbatch, fwd/bwd) unit runs on which device at which tick) under the
TaxoNN TDM frame model: one device-tick can co-issue one forward and one
backward unit, because the paper's time-division-multiplexed datapath
(``kernels.bp_fused_unit``) runs FP + BP + WU of one frame back-to-back on
the same PEs.  GPipe cannot co-issue — its loss barrier means no backward
work exists until every forward has drained — so its table is the forward
diagonal followed by the backward diagonal.  1F1B interleaves the two
diagonals in steady state and interleaved-1F1B additionally shrinks the
warm-up by splitting each device into virtual stages.  From the table each
schedule derives ``bubble_fraction(S, M)`` (idle device-ticks / total) and
``peak_activation_microbatches(S, M)`` (max in-flight forward activations
resident on one device) — the bubble/memory tradeoff GPipe vs 1F1B is
about.  ``(S-1)/(M+S-1)`` is GPipe's closed form (CATERPILLAR, Li &
Pedram 2017); 1F1B's fused frames land strictly below it for S >= 2.

See tests/test_pipeline_parallel.py for exactness and the bubble ordering,
and dist/hlo_analysis.py::per_tick_attribution for attributing compiled
collective-permute bytes to schedule ticks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Idle fraction of the GPipe schedule: (S-1) / (M + S - 1)."""
    s, m = num_stages, num_microbatches
    return (s - 1) / (m + s - 1)


# ---------------------------------------------------------------------------
# Tick tables (the cost model)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """One schedule instantiated at (S stages, M microbatches).

    ``fwd_tick[s, m]`` / ``bwd_tick[s, m]`` give the tick at which the
    forward / backward unit of microbatch m runs on stage s.  Everything
    else (bubble, peak memory) is derived from these two arrays.
    """
    num_stages: int
    num_microbatches: int
    num_devices: int
    num_virtual: int
    num_ticks: int
    fwd_tick: np.ndarray          # [S, M] int
    bwd_tick: np.ndarray          # [S, M] int
    busy_slots: int               # device-ticks with >= 1 unit issued
    bubble: float                 # 1 - busy / (num_ticks * num_devices)
    peak_activation_microbatches: int

    def stage_device(self, s: int) -> int:
        return s % self.num_devices


def _finish_plan(S: int, M: int, D: int, v: int, fwd: np.ndarray,
                 bwd: np.ndarray) -> SchedulePlan:
    """Derive span/bubble/peak-memory from the (fwd, bwd) tick arrays."""
    ticks = int(max(fwd.max(), bwd.max())) + 1
    # busy device-ticks: a fused (F, B) pair on one device is ONE busy slot
    busy = set()
    for s in range(S):
        for m in range(M):
            busy.add((s % D, int(fwd[s, m])))
            busy.add((s % D, int(bwd[s, m])))
    # peak in-flight activations per device: an activation is live from the
    # tick its forward issues until the tick its backward (the consumer)
    # issues
    peak = 0
    for d in range(D):
        stages = range(d, S, D)
        events = []                 # (+1 at fwd tick, -1 at bwd tick)
        for s in stages:
            for m in range(M):
                events.append((int(fwd[s, m]), 1))
                events.append((int(bwd[s, m]), -1))
        live = 0
        for _, delta in sorted(events):   # -1 sorts before +1 at equal ticks
            live += delta
            peak = max(peak, live)
    return SchedulePlan(
        num_stages=S, num_microbatches=M, num_devices=D, num_virtual=v,
        num_ticks=ticks, fwd_tick=fwd, bwd_tick=bwd, busy_slots=len(busy),
        bubble=1.0 - len(busy) / (ticks * D),
        peak_activation_microbatches=peak)


def _gpipe_plan(S: int, M: int) -> SchedulePlan:
    """All forwards, loss barrier, all backwards (two diagonals)."""
    fwd = np.zeros((S, M), np.int64)
    bwd = np.zeros((S, M), np.int64)
    t_flush = M + S - 1
    for s in range(S):
        for m in range(M):
            fwd[s, m] = m + s
            bwd[s, m] = t_flush + (S - 1 - s) + m
    return _finish_plan(S, M, S, 1, fwd, bwd)


def _one_f_one_b_plan(S: int, M: int) -> SchedulePlan:
    """Closed-form 1F1B on TDM fused frames: two interleaved diagonals.

    F(s, m) at tick s + m and B(s, m) at tick (2S-1-s) + m satisfy every
    dependency (F feeds forward one tick apart, B feeds backward one tick
    apart, and F(s, m) < B(s, m) since 2s < 2S-1), and in steady state a
    device co-issues one F and one B per tick — the paper's TDM frame.
    Span = M + 2S - 2 ticks after tick 0, so bubble = (S-1)/(M+2S-1) —
    strictly below GPipe's (S-1)/(M+S-1) for every S >= 2 — and in-flight
    activations at stage s cap at min(M, 2(S-s)-1) instead of GPipe's M.
    """
    s_idx = np.arange(S)[:, None]
    m_idx = np.arange(M)[None, :]
    fwd = np.broadcast_to(s_idx + m_idx, (S, M)).astype(np.int64)
    bwd = np.broadcast_to((2 * S - 1 - s_idx) + m_idx, (S, M)).astype(np.int64)
    return _finish_plan(S, M, S, 1, fwd, bwd)


def _interleaved_plan(S: int, M: int, v: int) -> SchedulePlan:
    """Greedy work-conserving simulation of interleaved-1F1B under the
    TDM fused-frame model: per tick a device issues at most one backward
    (lowest microbatch, deepest stage first) and one forward (subject to
    the per-stage in-flight cap that gives 1F1B its memory bound)."""
    D = S // v
    NOT_DONE = -1
    fwd = np.full((S, M), NOT_DONE, np.int64)
    bwd = np.full((S, M), NOT_DONE, np.int64)
    next_fwd = [0] * S                  # microbatches enter a stage in order
    next_bwd = [0] * S

    def fwd_ready(s: int, t: int) -> Optional[int]:
        m = next_fwd[s]
        if m >= M:
            return None
        if s > 0 and not (0 <= fwd[s - 1, m] < t):
            return None
        return m

    def bwd_ready(s: int, t: int) -> Optional[int]:
        m = next_bwd[s]
        if m >= M or not (0 <= fwd[s, m] < t):
            return None
        if s < S - 1 and not (0 <= bwd[s + 1, m] < t):
            return None
        return m

    def inflight(s: int) -> int:
        return next_fwd[s] - next_bwd[s]

    remaining = 2 * S * M
    t = 0
    while remaining:
        issued_any = False
        for relax_caps in (False, True):
            for d in range(D):
                stages = list(range(d, S, D))
                # one backward: lowest microbatch, deepest stage breaks ties
                cand = [(m, -s, s) for s in stages
                        for m in (bwd_ready(s, t),) if m is not None]
                b_issue = min(cand) if cand else None
                if b_issue is not None:
                    s = b_issue[2]
                    bwd[s, next_bwd[s]] = t
                    next_bwd[s] += 1
                    remaining -= 1
                    issued_any = True
                # one forward: earliest microbatch first, capped in-flight
                cand = [(m, s) for s in stages
                        for m in (fwd_ready(s, t),) if m is not None
                        and (relax_caps or inflight(s) < 2 * (S - s) - 1)]
                if cand:
                    s = min(cand)[1]
                    fwd[s, next_fwd[s]] = t
                    next_fwd[s] += 1
                    remaining -= 1
                    issued_any = True
            if issued_any:
                break
        assert issued_any, "1F1B simulation stalled (dependency bug)"
        t += 1
    return _finish_plan(S, M, D, v, fwd, bwd)


@functools.lru_cache(maxsize=None)
def _plan_cached(kind: str, S: int, M: int, v: int) -> SchedulePlan:
    if kind == "gpipe":
        return _gpipe_plan(S, M)
    if v == 1:
        return _one_f_one_b_plan(S, M)
    return _interleaved_plan(S, M, v)


# ---------------------------------------------------------------------------
# Schedule abstraction
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Schedule:
    """A pipeline schedule: stage placement + tick-table cost model."""
    name: str = "gpipe"
    num_virtual: int = 1          # virtual stages per device (interleaved)

    _kind = "gpipe"

    # -- validation / placement -------------------------------------------
    def validate(self, num_stages: int, num_microbatches: int = 1) -> None:
        if num_stages < 1 or num_microbatches < 1:
            raise ValueError(
                f"{self.name}: need num_stages >= 1 and num_microbatches >= "
                f"1, got S={num_stages}, M={num_microbatches}")
        if self.num_virtual < 1:
            raise ValueError(f"{self.name}: num_virtual must be >= 1, got "
                             f"{self.num_virtual}")
        if num_stages % self.num_virtual != 0:
            raise ValueError(
                f"{self.name}: num_stages={num_stages} does not divide into "
                f"num_virtual={self.num_virtual} virtual stages per device; "
                f"use a stage count divisible by the virtual-stage count")

    def num_devices(self, num_stages: int) -> int:
        return num_stages // self.num_virtual

    def stage_of_slot(self, num_stages: int) -> np.ndarray:
        """Storage order of the rotating buffer: slot j holds which stage.

        Device-major: with D devices and v virtual stages, slot (d*v + k)
        holds stage (k*D + d), so pinning the slot axis to the "pipe" mesh
        axis gives each device its round-robin virtual stages.
        """
        self.validate(num_stages)
        D = self.num_devices(num_stages)
        return np.add.outer(np.arange(D),
                            np.arange(self.num_virtual) * D).reshape(-1)

    # -- cost model --------------------------------------------------------
    def plan(self, num_stages: int, num_microbatches: int) -> SchedulePlan:
        self.validate(num_stages, num_microbatches)
        return _plan_cached(self._kind, num_stages, num_microbatches,
                            self.num_virtual)

    def bubble_fraction(self, num_stages: int, num_microbatches: int) -> float:
        """Idle fraction of device-ticks in this schedule's tick table."""
        return self.plan(num_stages, num_microbatches).bubble

    def peak_activation_microbatches(self, num_stages: int,
                                     num_microbatches: int) -> int:
        """Max forward activations simultaneously resident on one device."""
        return self.plan(num_stages,
                         num_microbatches).peak_activation_microbatches

    def peak_activation_bytes(self, num_stages: int, num_microbatches: int,
                              microbatch_bytes: int) -> int:
        """Peak per-device activation memory, given one stage's activation
        footprint for one microbatch."""
        return (self.peak_activation_microbatches(num_stages,
                                                  num_microbatches)
                * int(microbatch_bytes))

    def summary(self, num_stages: int, num_microbatches: int) -> Dict:
        p = self.plan(num_stages, num_microbatches)
        return {
            "schedule": self.name,
            "num_stages": p.num_stages,
            "num_microbatches": p.num_microbatches,
            "num_devices": p.num_devices,
            "num_virtual": p.num_virtual,
            "ticks": p.num_ticks,
            "bubble_fraction": p.bubble,
            "peak_activation_microbatches": p.peak_activation_microbatches,
        }


@dataclasses.dataclass(frozen=True)
class GPipeSchedule(Schedule):
    """All-forward / flush / all-backward; peak memory grows with M."""
    name: str = "gpipe"
    _kind = "gpipe"

    def validate(self, num_stages: int, num_microbatches: int = 1) -> None:
        if self.num_virtual != 1:
            raise ValueError("gpipe has no virtual stages; use the "
                             "interleaved schedule for num_virtual > 1")
        super().validate(num_stages, num_microbatches)

    def bubble_fraction(self, num_stages: int, num_microbatches: int) -> float:
        self.validate(num_stages, num_microbatches)
        return bubble_fraction(num_stages, num_microbatches)  # closed form


@dataclasses.dataclass(frozen=True)
class OneFOneBSchedule(Schedule):
    """PipeDream-flush 1F1B on TaxoNN TDM frames: steady-state ticks fuse
    one forward with one backward, bounding in-flight activations by ~S
    instead of M and shrinking the bubble below GPipe's."""
    name: str = "1f1b"
    _kind = "1f1b"

    def validate(self, num_stages: int, num_microbatches: int = 1) -> None:
        if self.num_virtual != 1:
            raise ValueError("1f1b runs one stage per device; use the "
                             "interleaved schedule for num_virtual > 1")
        super().validate(num_stages, num_microbatches)


@dataclasses.dataclass(frozen=True)
class Interleaved1F1BSchedule(Schedule):
    """1F1B with ``num_virtual`` round-robin virtual stages per device
    (Megatron-style): the warm-up diagonal spans D = S / v devices instead
    of S, trading bubble for more collective-permute hops per tick."""
    name: str = "interleaved"
    num_virtual: int = 2
    _kind = "1f1b"


SCHEDULES: Dict[str, Callable[..., Schedule]] = {
    "gpipe": GPipeSchedule,
    "1f1b": OneFOneBSchedule,
    "interleaved": Interleaved1F1BSchedule,
}


def get_schedule(spec: Union[str, Schedule, None] = "gpipe",
                 num_virtual: Optional[int] = None) -> Schedule:
    """Resolve a schedule name ("gpipe" | "1f1b" | "interleaved") or pass
    a ``Schedule`` instance through.  ``num_virtual`` overrides the
    virtual-stage count for the interleaved schedule."""
    if spec is None:
        spec = "gpipe"
    if isinstance(spec, Schedule):
        if num_virtual is not None and num_virtual != spec.num_virtual:
            return dataclasses.replace(spec, num_virtual=num_virtual)
        return spec
    if spec not in SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {spec!r}; expected one "
                         f"of {tuple(SCHEDULES)}")
    kwargs = {}
    if num_virtual is not None:
        if spec != "interleaved" and num_virtual != 1:
            raise ValueError(f"schedule {spec!r} does not take virtual "
                             f"stages (num_virtual={num_virtual})")
        if spec == "interleaved":
            kwargs["num_virtual"] = num_virtual
    return SCHEDULES[spec](**kwargs)


# ---------------------------------------------------------------------------
# Execution (pure differentiable JAX)
# ---------------------------------------------------------------------------

def _stage_constrain(buf, mesh):
    """Pin the rotating buffer's slot axis to the "pipe" mesh axis."""
    if mesh is None or "pipe" not in getattr(mesh, "axis_names", ()):
        return buf
    if buf.shape[0] % dict(mesh.shape)["pipe"] != 0:
        return buf
    spec = P("pipe", *([None] * (buf.ndim - 1)))
    try:
        return jax.lax.with_sharding_constraint(
            buf, NamedSharding(mesh, spec))
    except Exception:  # eager call outside a partitionable context
        return buf


def _slot_maps(sched: Schedule, S: int) -> Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray, bool]:
    stage_of_slot = sched.stage_of_slot(S)
    slot_of_stage = np.argsort(stage_of_slot)
    route = slot_of_stage[(stage_of_slot - 1) % S]   # dst slot <- src slot
    identity = bool((stage_of_slot == np.arange(S)).all())
    return stage_of_slot, slot_of_stage, route, identity


def pipeline_apply(stage_params, x, body: Callable,
                   mesh=None,
                   schedule: Union[str, Schedule, None] = "gpipe"):
    """Apply an S-stage pipeline to M microbatches under a schedule.

    stage_params : pytree whose leaves carry a leading stage axis [S, ...]
    x            : pytree whose leaves carry a leading microbatch axis
                   [M, microbatch...].  A bare array is the common case; a
                   pytree lets side values ride the rotating buffer with
                   the activation — e.g. a per-microbatch aux-loss
                   accumulator each stage adds to (reduce-class operand,
                   summed by the caller after the drain) or the microbatch
                   index itself, which stages use to slice broadcast-class
                   operands (an encoder output fan-out) down to their
                   current microbatch
    body         : body(stage_params_s, v) -> v', one stage on one
                   microbatch value; must preserve the value's structure
                   and leaf shapes so the result can recirculate
    mesh         : optional mesh with a "pipe" axis to pin stages to devices
    schedule     : "gpipe" | "1f1b" | "interleaved" or a Schedule; selects
                   the stage->device placement (interleaved permutes the
                   buffer storage so each device holds its round-robin
                   virtual stages) and the cost model reported by
                   ``Schedule.summary``.  All schedules compute the same
                   function: the result is bit-identical to running the
                   stages sequentially over each microbatch, and gradients
                   (the scan's transpose) match the sequential reference.

    Returns a pytree shaped like ``x`` ([M, microbatch...] leaves).
    """
    sched = get_schedule(schedule)
    S = jax.tree.leaves(stage_params)[0].shape[0]
    M = jax.tree.leaves(x)[0].shape[0]
    sched.validate(S, M)
    stage_of_slot, slot_of_stage, route, identity = _slot_maps(sched, S)
    in_slot = int(slot_of_stage[0])
    out_slot = int(slot_of_stage[S - 1])
    T = M + S - 1

    if identity:
        params_slots = stage_params
    else:                       # device-major storage for virtual stages
        gather = jnp.asarray(stage_of_slot)
        params_slots = jax.tree.map(lambda a: a[gather], stage_params)
        route_idx = jnp.asarray(route)

    def tick(carry, t):
        buf, outs = carry                    # buf [S, mb...]: slot inputs
        # feed microbatch t into stage 0's slot (garbage recirculates after
        # drain; its outputs fall past tick T and are never collected)
        t_in = jnp.clip(t, 0, M - 1)
        buf = jax.tree.map(
            lambda b, a: b.at[in_slot].set(jnp.where(
                t < M,
                lax.dynamic_index_in_dim(a, t_in, 0, keepdims=False),
                b[in_slot])),
            buf, x)
        buf = jax.tree.map(lambda b: _stage_constrain(b, mesh), buf)
        new = jax.vmap(body)(params_slots, buf)  # all slots, one tick
        # stage S-1's slot finished microbatch t-(S-1): write it out
        # (predicated — warm-up ticks produce garbage that must not touch
        # outs or grads)
        idx = t - (S - 1)
        idx_c = jnp.maximum(idx, 0)

        def write(o, n):
            cur = lax.dynamic_index_in_dim(o, idx_c, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                o, jnp.where(idx >= 0, n[out_slot], cur), idx_c, 0)

        outs = jax.tree.map(write, outs, new)
        # route: the slot holding stage s feeds the slot holding stage s+1
        # (identity placement lowers to the classic rotate-by-one)
        nxt = jax.tree.map(
            lambda n: jnp.roll(n, 1, axis=0) if identity else n[route_idx],
            new)
        return (nxt, outs), None

    buf0 = jax.tree.map(
        lambda a: jnp.zeros((S,) + a.shape[1:], a.dtype), x)
    outs0 = jax.tree.map(jnp.zeros_like, x)
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
    return outs
