"""From a profiler trace to device numbers: busy and idle time of each
chip over the traced window, the device operations that took the most
time, and the idle time by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``Event`` tuples; ``reduce`` works on those alone, so it can be checked
on a small recorded trace or on events made by hand.

- Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window; idle share is 1 - busy / window, averaged over the
  chips.
- The window is the host annotation ``WINDOW`` around the traced part
  of the run.
- An idle gap takes the name of the host annotation (one of ``LABELS``)
  that overlaps most of it, else ``other``.
- A step program's device time is the union of the device operations
  whose midpoint lies inside one of its host annotations (``STEPS``):
  the host dispatches the program and waits for its result inside the
  annotation, so its operations run there, and the host's own work
  before and after (``new_batch``, ``sample_sync``) is not counted.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import NamedTuple

import numpy as np

WINDOW = "traced_window"
LABELS = ("new_batch", "prefill", "decode_step", "sample_sync")
STEPS = ("prefill", "decode_step")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
_BACK = 64


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(log_dir: str) -> list[Event]:
    """Every event of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    host_names = set(LABELS) | {WINDOW}
    out = []
    for p in data.planes:
        dev = p.name.startswith(DEVICE_PREFIX)
        if not dev and "/host:" not in p.name:
            continue
        for ln in p.lines:
            if dev and ln.name != OPS_LINE:
                continue
            out.extend(Event(p.name, ln.name, e.name, e.start_ns,
                             e.duration_ns)
                       for e in ln.events if dev or e.name in host_names)
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _union_s(starts, ends) -> float:
    """Total length of the union of [start, end) intervals."""
    if len(starts) == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return float(np.sum(reach[last] - s[first]))


def _step_busy(evs, spans) -> float:
    """Union of the ops of ``evs`` whose midpoint lies inside one of the
    sorted, disjoint host ``spans`` [(start, end)]."""
    if not spans or not evs:
        return 0.0
    st = np.array([e.start_ns for e in evs], np.float64)
    en = st + np.array([e.dur_ns for e in evs], np.float64)
    mid = (st + en) / 2
    hs = np.array([a for a, _ in spans], np.float64)
    he = np.array([b for _, b in spans], np.float64)
    i = np.searchsorted(hs, mid, side="right") - 1
    inside = (i >= 0) & (mid < he[np.maximum(i, 0)])
    return _union_s(st[inside], en[inside])


def reduce(events: list[Event]) -> dict | None:
    """``busy_s`` and ``window_s`` (seconds, busy averaged over chips),
    ``device_ops`` and ``idle_gaps`` (top ``TOP`` [name, seconds], summed
    over chips), ``n_devices``, and per step program of ``STEPS`` its
    host annotations in the window (``steps[name]["n"]``) and its device
    time averaged over chips (``steps[name]["busy_s"]``); None where the
    trace holds no window or no device operation."""
    win = [e for e in events if e.name == WINDOW and "/host:" in e.plane]
    ops = defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE:
            ops[e.plane].append(e)
    if not win or not ops:
        return None
    lo = win[0].start_ns
    hi = lo + win[0].dur_ns
    host = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                  for e in events
                  if e.name in LABELS and "/host:" in e.plane)
    starts = [h[0] for h in host]
    spans = {k: [(a, b) for a, b, n in host if n == k and lo <= a < hi]
             for k in STEPS}
    step_busy = dict.fromkeys(STEPS, 0.0)
    per_op = defaultdict(float)
    per_gap = defaultdict(float)
    busy = 0.0
    for evs in ops.values():
        iv = _clip([(e.start_ns, e.start_ns + e.dur_ns) for e in evs],
                   lo, hi)
        for name, s, t in _self_times(evs):
            s, t = max(s, lo), min(t, hi)
            if t > s:
                per_op[name] += (t - s) / 1e9
        for k in STEPS:
            step_busy[k] += _step_busy(evs, spans[k])
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for seg in merged for x in seg] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                per_gap[_label(host, starts, s, t)] += (t - s) / 1e9
    n = len(ops)
    return {"busy_s": busy / n / 1e9, "window_s": (hi - lo) / 1e9,
            "n_devices": n,
            "steps": {k: {"n": len(spans[k]),
                          "busy_s": step_busy[k] / n / 1e9} for k in STEPS},
            "device_ops": _top(per_op), "idle_gaps": _top(per_gap)}


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _self_times(evs):
    """(name, start, end) of each op's own pieces: an op that encloses
    others (a ``while`` around its body) keeps only the time in which
    none of them runs."""
    out = []
    stack = []          # [name, cursor, end] of enclosing ops
    for e in sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns)):
        s, t = e.start_ns, e.start_ns + e.dur_ns
        while stack and stack[-1][2] <= s:
            name, cur, end = stack.pop()
            out.append((name, cur, end))
        if stack:
            top = stack[-1]
            out.append((top[0], top[1], s))
            top[1] = t
        stack.append([_op_name(e.name), s, t])
    while stack:
        name, cur, end = stack.pop()
        out.append((name, cur, end))
        if stack:
            stack[-1][1] = max(stack[-1][1], end)
    return [(n, s, t) for n, s, t in out if t > s]


def _label(host, starts, s, t) -> str:
    """The host annotation overlapping most of [s, t); annotations do
    not nest, so only those starting before ``t`` and no more than
    ``_BACK`` entries earlier can overlap."""
    best, name = 0.0, "other"
    i = bisect.bisect_left(starts, t)
    for hs, he, hn in host[max(0, i - _BACK):i]:
        o = min(he, t) - max(hs, s)
        if o > best:
            best, name = o, hn
    return name


def _top(d: dict) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            [:TOP]]
