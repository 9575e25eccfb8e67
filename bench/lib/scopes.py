"""Device time of the train step by layer, read from its ops' scope paths.

The program names the layer of each op with ``jax.named_scope`` and each
Pallas kernel with its ``name``; both land in the op's HLO ``op_name``
metadata, e.g. ``jit(step)/gchain/while/body/transpose(jvp(block))/
dense_unit/fxp_matmul/pallas_call``.  The layers, from the program's
``repro.util.scopes``:

  embed       the token embedding and its input-side gradient
  block       one layer's forward, and its recompute inside the G-chain
  attention   scores, mask, softmax and P @ V (not the projections)
  dense_unit  quantize in, kernel, rescale out; its dx and dW legs
  head_loss   final norm, logits, cross-entropy and their backward
  gchain      one layer's VJP and G quantization in the backward scan
  update      the dW reduce, momentum and weight update

A device trace names an op by its HLO instruction ("%fusion.12 = ...").
Where the event's name carries no ``op_name``, the path is looked up by
instruction name in the optimised HLO text of the cell's step
(``step_hlo``): the run's own step lowered again from its state's shapes,
which the window's own compile left in the persistent compilation cache.  An
instruction the compiler made without an op_name takes a neighbour's
(``hlo_op_names``).

An op belongs to the innermost layer on its path (``layer_seconds``);
``scope_s`` counts an op under a scope at any depth.  Both clip to the
window and leave out loops and calls, as ``trace_reader`` does.  A program
whose ops carry no layer scope reads nothing (None), and so does a run
without a trace.
"""
from __future__ import annotations

import functools
import json
import re
import sys
import time
import traceback

from bench.lib import trace_reader

LAYERS = ("embed", "block", "attention", "dense_unit", "head_loss", "gchain",
          "update")
INT8_KERNELS = ("fxp_matmul", "bp_gstep", "sgd_dw_update")
UNSCOPED = "unscoped"

_WORD = re.compile(r"[A-Za-z_][\w\-]*")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_STACK_ROOT = re.compile(r"[\w\-]+\([^/\[]*\)(/|$)")


def instruction(event_name: str) -> str:
    """The HLO instruction an event names: "fusion.12" of
    "%fusion.12 = f32[8]{0} fusion(...)"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@functools.lru_cache(maxsize=1 << 16)
def path_words(path: str) -> tuple:
    """The names on an op_name path; a transformed scope
    ("transpose(jvp(block))") gives each of its names, innermost last.  A
    path that starts over from its root part way (a branch inside a
    kernel's loop repeats "jit(step)/...") is read up to that point."""
    parts = path.split("/")
    if parts[0] in parts[1:]:
        parts = parts[:parts.index(parts[0], 1)]
    return tuple(_WORD.findall("/".join(parts)))


def layers_of(path) -> tuple:
    """The layer scopes on a path, outermost first.  Only a name-stack path
    ("jit(step)/...") has any: not None, nor an argument's op_name
    ("params['embed']")."""
    if not path or not _STACK_ROOT.match(path):
        return ()
    return tuple(w for w in path_words(path) if w in LAYERS)


def innermost(path):
    found = layers_of(path)
    return found[-1] if found else None


def hlo_op_names(text: str) -> dict:
    """{instruction: op_name} for the instructions of an optimised HLO text.

    The compiler makes some instructions without an op_name: a fusion
    then takes the op_name of its fused computation's root (or of its
    first instruction that has one); any other instruction, such as a copy
    of a loop's result into the step's output or a zero-fill of a loop's
    output buffer, takes the op_name of the nearest instruction of its own
    computation whose op_name names a layer, through its operands first and
    then its users.  An instruction with no such neighbour keeps what it
    has, or is left out."""
    own, calls, comp_of, roots, edges = {}, {}, {}, {}, {}
    comp, members = "", {"": []}
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            members[comp] = []
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        comp_of[name] = comp
        members[comp].append(name)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = name
        on = _OP_NAME.search(line)
        if on:
            own[name] = on.group(1)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        edges[name] = _REF.findall(line[m.end():])

    def inner(name):
        """op_name of a fusion's fused computation."""
        sub = calls.get(name)
        if sub not in members:
            return None
        if roots.get(sub) in own:
            return own[roots[sub]]
        return next((own[i] for i in members[sub] if i in own), None)

    operands = {n: [o for o in edges[n] if comp_of.get(o) == comp_of[n]]
                for n in edges}
    users = {}
    for n, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(n)
    direct = {n: own.get(n) or inner(n) for n in comp_of}
    out = {n: p for n, p in direct.items() if p}
    for n in comp_of:
        if n in out:
            continue
        for step in (operands, users):
            seen, frontier = {n}, [n]
            for _ in range(4):
                frontier = [x for f in frontier for x in step.get(f, ())
                            if x not in seen]
                seen.update(frontier)
                hit = next((direct[x] for x in frontier
                            if layers_of(direct[x])), None)
                if hit or not frontier:
                    break
            if hit:
                out[n] = hit
                break
    return out


def op_scopes(trace: dict, hlo_text=None) -> dict:
    """{event name: op_name path} for the device ops of a trace, from the
    event's own name, else from ``hlo_text`` by instruction name."""
    by_instr = hlo_op_names(hlo_text) if hlo_text else {}
    out = {}
    for evs in trace["devices"].values():
        for name, _, _ in evs:
            if name in out:
                continue
            m = _OP_NAME.search(name)
            path = m.group(1) if m else by_instr.get(instruction(name))
            if path is not None:
                out[name] = path
    return out


def _has_layers(scopes: dict) -> bool:
    return any(layers_of(p) for p in scopes.values())


def _in_layers(trace: dict, window: tuple) -> bool:
    """Whether any op inside the window is under a layer scope."""
    return any(layers_of(path) for _, path, _ in _clipped(trace, window))


def step_hlo(rec: dict) -> str:
    """The optimised HLO text of the record's train step: the window's own
    jitted step, lowered from its state's shapes by the run that built it
    (``train_loop.TrainRun.lowered``)."""
    return rec["run"].lowered().compile().as_text()


def scoped_trace(rec: dict):
    """The record's trace with ``"op_scopes"`` added, or None where the run
    kept no trace or no op of its window carries a layer scope.  The first call also
    logs the seconds under each innermost layer (``[bench] scopes:``)."""
    trace = rec.get("trace")
    if trace is None or not trace["devices"]:
        return None
    if "op_scopes" not in trace:
        scopes = op_scopes(trace)
        if not _has_layers(scopes):
            t0 = time.perf_counter()
            try:
                scopes = op_scopes(trace, step_hlo(rec))
            except Exception:  # noqa: BLE001 - the metrics are left out
                _log("no step HLO for the scope paths:\n"
                     + traceback.format_exc())
            else:
                _log(f"step HLO for the scope paths: "
                     f"{time.perf_counter() - t0:.3f} s")
        trace["op_scopes"] = scopes
        trace["scoped"] = _in_layers(trace, rec["trace_window"])
        if trace["scoped"]:
            secs = layer_seconds(trace, rec["trace_window"])
            _log("scopes: " + json.dumps(
                {k: round(v, 6) for k, v in secs.items()}))
    return trace if trace["scoped"] else None


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _clipped(trace: dict, window: tuple):
    """(event name, path, seconds inside the window) of each op, loops and
    calls left out, per device."""
    lo, hi = window
    scopes = trace.get("op_scopes", {})
    for evs in trace["devices"].values():
        for name, s, d in evs:
            t = min(s + d, hi) - max(s, lo)
            if t > 0 and not trace_reader._is_container(name):
                yield name, scopes.get(name), t / 1e9


def scope_s(trace: dict, window: tuple, scope: str) -> float:
    """Device time of the ops under ``scope`` at any depth, averaged over
    the devices."""
    tot = sum(t for _, path, t in _clipped(trace, window)
              if scope in layers_of(path))
    return tot / max(len(trace["devices"]), 1)


def is_kernel(name: str, path, kernels) -> bool:
    """Whether an op is one of the named kernels: by its instruction (the
    compiler names a kernel's call after it) or by its path."""
    base = re.sub(r"\.\d+$", "", instruction(name))
    return base in kernels or bool(path and any(
        k in path_words(path) for k in kernels))


def kernel_s(trace: dict, window: tuple, kernels, scope=None) -> float:
    """Device time of the named kernels (under ``scope`` if given),
    averaged over the devices."""
    tot = sum(t for name, path, t in _clipped(trace, window)
              if is_kernel(name, path, kernels)
              and (scope is None or scope in layers_of(path)))
    return tot / max(len(trace["devices"]), 1)


def layer_seconds(trace: dict, window: tuple) -> dict:
    """{layer: seconds} by each op's innermost layer, and ``unscoped`` for
    ops under none, averaged over the devices."""
    out = dict.fromkeys(LAYERS + (UNSCOPED,), 0.0)
    for _, path, t in _clipped(trace, window):
        out[innermost(path) or UNSCOPED] += t
    k = max(len(trace["devices"]), 1)
    return {name: t / k for name, t in out.items()}


def busy_share(rec: dict, scope: str):
    """Percent of the window's busy device time spent under ``scope``."""
    trace = scoped_trace(rec)
    if trace is None:
        return None
    win = rec["trace_window"]
    busy = trace_reader.busy_s(trace, win)
    return 100.0 * scope_s(trace, win, scope) / busy if busy > 0 else None
