"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

A trace is read into plain tuples (``load_xplane``) so that every
reduction below also runs on a hand-built trace:

* ``devices``: for each device plane that has an "XLA Ops" line, its
  operations as ``(name, start_ns, dur_ns)``.  On a TPU an operation's
  name is its HLO instruction (``%fusion.12 = bf16[...] fusion(...)``);
  a ``while`` loop's event spans the operations of its body, and the
  gaps between them;
* ``spans``: the benchmark's own host spans (``TraceAnnotation`` names
  starting with ``bench.``) as ``(name, start_ns, end_ns)``.

Busy time is the union of a device's operation intervals inside the
window (loops and calls left out), averaged over the devices; idle share
is 1 - busy / window.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                continue  # e.g. "/device:CUSTOM:Megascale Trace"
            devices[plane.name] = [(e.name, int(e.start_ns),
                                    int(e.duration_ns))
                                   for ln in lines for e in ln.events]
        else:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
    return {"devices": devices, "spans": spans}


def window_of(trace: dict, name: str) -> tuple:
    """(start_ns, end_ns) of the first host span called ``name``."""
    for n, s, e in trace["spans"]:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def _is_container(name: str) -> bool:
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head) in CONTAINERS


def _intervals(events, lo, hi):
    """Merged [start, end) of the operations inside (lo, hi); loops and
    calls are left out, since their events span their bodies' gaps."""
    out = []
    for name, s, d in events:
        if _is_container(name):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    out.sort()
    merged = []
    for a, b in out:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_s(trace: dict, window: tuple) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    lo, hi = window
    tot = sum(sum(b - a for a, b in _intervals(evs, lo, hi))
              for evs in devs.values())
    return tot / len(devs) / 1e9


def idle_share(trace: dict, window: tuple):
    """Percent of the window in which no operation ran (None without a
    device plane)."""
    if not trace["devices"]:
        return None
    lo, hi = window
    return 100.0 * (1.0 - busy_s(trace, window) / ((hi - lo) / 1e9))


def kernel_s(trace: dict, window: tuple, patterns) -> float:
    """Summed device time of operations whose name matches one of the
    regular expressions, averaged over the devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    lo, hi = window
    tot = 0
    for evs in devs.values():
        for name, s, d in evs:
            if rx.search(name):
                tot += max(0, min(s + d, hi) - max(s, lo))
    return tot / len(devs) / 1e9


def _result_type(rhs: str) -> str:
    """The result type at the start of an HLO instruction's right-hand
    side, layouts dropped: "f32[16,1024]" or "(f32[16], bf16[4,8])"."""
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rhs):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rhs[:i + 1]
    return rhs


def op_family(name: str) -> str:
    """An operation's instruction name without its numeric suffix, with its
    result type where the trace gives the instruction: "fusion.7" is
    "fusion", "%fusion.12 = bf16[8]{0} fusion(...)" is "fusion bf16[8]";
    a custom call also keeps its first operand's element type, which tells
    the int8 kernels ("custom-call(s8) f32[...]") from the others."""
    head = name.split(" = ", 1)
    base = re.sub(r"\.\d+$", "", head[0].lstrip("%")) or head[0]
    if len(head) == 1:
        return base
    m = re.search(r"custom-call\((\w+)\[", head[1])
    if m:
        base = f"custom-call({m.group(1)})"
    return f"{base} {_result_type(head[1])}"



def top_ops(trace: dict, window: tuple, n: int = 10) -> list:
    """The ``n`` operation families that took most device time, as
    [name, seconds] averaged over the devices.  Loops and calls are left
    out: their events span the operations of their bodies, which are
    counted themselves."""
    devs = trace["devices"]
    lo, hi = window
    acc = {}
    for evs in devs.values():
        for name, s, d in evs:
            t = max(0, min(s + d, hi) - max(s, lo))
            k = op_family(name)
            if t and not _is_container(name):
                acc[k] = acc.get(k, 0) + t
    k = max(len(devs), 1)
    items = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in items]


def idle_gaps(trace: dict, window: tuple, n: int = 10) -> list:
    """The ``n`` longest device-idle gaps in the window (first device), each
    as [label, seconds]: the label is the innermost benchmark host span
    open at the gap's midpoint, or "no span"."""
    devs = trace["devices"]
    if not devs:
        return []
    lo, hi = window
    busy = _intervals(devs[sorted(devs)[0]], lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        open_ = [(e - s, name) for name, s, e in trace["spans"]
                 if s <= mid <= e and name != "bench.window"]
        label = min(open_)[1] if open_ else "no span"
        out.append([label, (b - a) / 1e9])
    return out
