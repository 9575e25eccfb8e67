"""Counts from shapes alone that every model family shares.

A family's own counts (model FLOPs of a train step, its dense-unit grids,
its parameters) live in its module under ``bench/families``.
"""
from __future__ import annotations


def keys_attended(start: int, count: int, window=None) -> int:
    """Sum over query positions start..start+count-1 of the keys the causal
    mask (and the sliding window, if any) admits."""
    total = 0
    for p in range(start, start + count):
        total += p + 1 if window is None else min(p + 1, window)
    return total


def least_time_s(grids, ops_per_s: float, bytes_per_s: float) -> float:
    """Sum over grids of max(2MNK / peak ops, bytes / peak bandwidth), the
    bytes being int8 operands in and an f32 result out."""
    total = 0.0
    for mm, nn, kk in grids:
        total += max(2.0 * mm * nn * kk / ops_per_s,
                     (mm * kk + kk * nn + 4.0 * mm * nn) / bytes_per_s)
    return total
