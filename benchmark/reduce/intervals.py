"""Interval arithmetic on (start, end) pairs, in whatever unit the caller
uses.  Everything a trace metric needs: union, gaps, how much of one set
another leaves exposed, and self time of nested events."""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points.  Touching
    intervals merge; empty ones vanish."""
    out: List[Interval] = []
    for lo, hi in sorted((a, b) for a, b in intervals if b > a):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if min(b, hi) > max(a, lo)]


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``merged`` (a union) leaves uncovered inside [lo, hi]."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def exposed(those: Iterable[Interval], cover: Iterable[Interval]) -> float:
    """Length of the part of ``those`` during which nothing of ``cover``
    runs (a collective's time that no compute hides)."""
    those, cover = union(those), union(cover)
    return sum(total(gaps(cover, lo, hi)) for lo, hi in those)


def self_times(events: Iterable[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """(name, own seconds) of events on one line, where an event that lies
    inside another (a loop's body inside the loop) is taken off its
    parent's time."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []                       # [end, name, own]
    for lo, hi, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= lo:
            end, n, own = stack.pop()
            out.append((n, own))
        if stack:
            stack[-1][2] -= min(hi, stack[-1][0]) - lo
        stack.append([hi, name, hi - lo])
    while stack:
        end, n, own = stack.pop()
        out.append((n, own))
    return out


def leaves(events: Iterable[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """The events that hold no other event (a loop is not a leaf, the
    operations of its body are)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (lo, hi, name) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or not (nxt[0] < hi and nxt[1] <= hi):
            out.append((lo, hi, name))
    return out
