"""From a profiler trace to the step programs' device time by block, and
the host's time to dispatch each decode step.

The program names its blocks with ``jax.named_scope`` (today
``repro.obs.BLOCKS``: ``attn_proj``, ``attn_core``, ``ffn``, ``head``;
this module keeps no copy of them) and calls each compiled step program
inside a host annotation of its own (``DISPATCH``, made by
``repro.launch.serve.compile_step_fns``).  A device trace names an
operation only by its HLO instruction, so the block of an operation
comes from the compiled program's text: ``maps`` reads it once, after
the trace, through ``repro.obs.op_blocks``.

- ``load`` reads the newest ``.xplane.pb`` under a directory into
  ``tracing.Event``s: every device operation, and the host annotations
  of the window, the step programs (``tracing.STEPS``) and their
  dispatches.
- ``reduce(events, maps)``, per step program: its operations, chosen as
  ``tracing.reduce`` chooses them (midpoint inside one of the program's
  host annotations in the window); their union (``busy_s``, the same
  number as ``tracing.reduce``'s ``steps[...]["busy_s"]``); and each
  operation's self time (``tracing._self_times``: a ``while`` keeps
  only the time in which none of its body runs) summed by the block,
  whatever its name, that the program's map gives the operation,
  ``other`` where the map has none (``blocks``: every block the map
  names, and ``other``; ``other_ops`` holds those operations' own times
  by name).  Where no two operations of a chip overlap but by
  nesting, the blocks sum to ``busy_s``.  Seconds, averaged over the
  chips.  Besides, the length of each ``decode_dispatch`` annotation in
  the window (``dispatch_s``), on the host's clock alone.
- ``per_step_ms`` and ``launch_ms`` give ms per traced step, or None
  where the trace's count of steps is not what the harness served;
  ``read_ms`` is what a block metric (``metrics/<program>_<block>_ms.py``)
  reads from a run's record.

The dispatch is read as the host's span, not as the device's idle time
inside it: in a TPU trace the device's clock is offset from the host's
(on a TPU v5e its operations appeared 0.4-1.9 ms before the host call
that launched them), which is more than a dispatch takes.  With the two
clocks put right by the trace's own order of events, the device was
idle through 98% or more of the median decode dispatch: the serving
loop waits for each token before it dispatches the next step.
"""
from __future__ import annotations

import glob
import os
import statistics
from collections import defaultdict

import numpy as np

from chipbench import tracing

MATMUL = ("attn_proj", "ffn", "head")     # the blocks that are matmuls
OTHER = "other"
DISPATCH = ("prefill_dispatch", "decode_dispatch")
PROGRAMS = {"prefill": "prefill", "decode_step": "step"}   # -> Server


def maps(server) -> dict | None:
    """HLO operation name -> block, per step program of ``server``
    (``loop.Server``); None where the program does not expose its
    compiled executables or names no blocks."""
    try:
        from repro.obs import op_blocks
    except ImportError:
        return None
    out = {}
    for k, attr in PROGRAMS.items():
        compiled = getattr(getattr(server, attr), "compiled", None)
        if compiled is None:
            return None
        out[k] = op_blocks(compiled)
    return out if any(out.values()) else None


def load(log_dir: str) -> list[tracing.Event]:
    """The device operations and the host annotations this reduction
    reads, of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    host_names = set(tracing.STEPS) | set(DISPATCH) | {tracing.WINDOW}
    out = []
    for p in data.planes:
        dev = p.name.startswith(tracing.DEVICE_PREFIX)
        if not dev and "/host:" not in p.name:
            continue
        for ln in p.lines:
            if dev and ln.name != tracing.OPS_LINE:
                continue
            out.extend(tracing.Event(p.name, ln.name, e.name, e.start_ns,
                                     e.duration_ns)
                       for e in ln.events if dev or e.name in host_names)
    return out


def _inside(evs, spans) -> list:
    """The events of ``evs`` whose midpoint lies inside one of the
    sorted, disjoint ``spans`` [(start, end)]."""
    if not spans or not evs:
        return []
    st = np.array([e.start_ns for e in evs], np.float64)
    mid = st + np.array([e.dur_ns for e in evs], np.float64) / 2
    hs = np.array([a for a, _ in spans], np.float64)
    he = np.array([b for _, b in spans], np.float64)
    i = np.searchsorted(hs, mid, side="right") - 1
    keep = (i >= 0) & (mid < he[np.maximum(i, 0)])
    return [e for e, k in zip(evs, keep) if k]


def reduce(events: list[tracing.Event], maps: dict | None) -> dict | None:
    """``steps[program]``: ``n`` host annotations in the window,
    ``busy_s``, ``blocks`` {block or ``other``: seconds} and
    ``other_ops`` {operation: seconds}; ``dispatch_s``: the seconds of
    each ``decode_dispatch``; ``n_devices``.  None
    where there are no maps, or the trace holds no window or no device
    operation."""
    win = [e for e in events
           if e.name == tracing.WINDOW and "/host:" in e.plane]
    ops = defaultdict(list)
    for e in events:
        if e.plane.startswith(tracing.DEVICE_PREFIX) \
                and e.line == tracing.OPS_LINE:
            ops[e.plane].append(e)
    if not maps or not win or not ops:
        return None
    lo = win[0].start_ns
    hi = lo + win[0].dur_ns
    spans = defaultdict(list)
    for e in events:
        if "/host:" in e.plane and e.name != tracing.WINDOW \
                and lo <= e.start_ns < hi:
            spans[e.name].append((e.start_ns, e.start_ns + e.dur_ns))
    for v in spans.values():
        v.sort()
    steps = {k: {"n": len(spans[k]), "busy_s": 0.0,
                 "blocks": dict.fromkeys(
                     sorted(set(maps[k].values())) + [OTHER], 0.0),
                 "other_ops": defaultdict(float)}
             for k in tracing.STEPS}
    n = len(ops)
    for evs in ops.values():
        for k in tracing.STEPS:
            sel = _inside(evs, spans[k])
            steps[k]["busy_s"] += tracing._union_s(
                [e.start_ns for e in sel],
                [e.start_ns + e.dur_ns for e in sel]) / n / 1e9
            blocks, other = steps[k]["blocks"], steps[k]["other_ops"]
            for name, s, t in tracing._self_times(sel):
                block = maps[k].get(name, OTHER)
                blocks[block] += (t - s) / n / 1e9
                if block == OTHER:
                    other[name] += (t - s) / n / 1e9
    return {"n_devices": n, "steps": steps,
            "dispatch_s": [(b - a) / 1e9
                           for a, b in spans["decode_dispatch"]]}


def per_step_ms(red: dict | None, program: str, served: int) -> dict | None:
    """ms per step of ``program`` in each block and ``other``; None where
    there is no reduction or its count of steps is not ``served``."""
    if not red or not served:
        return None
    s = red["steps"][program]
    if s["n"] != served:
        return None
    return {b: 1e3 * v / served for b, v in s["blocks"].items()}


def read_ms(r, program: str, names) -> float | None:
    """The ms per traced step of ``program`` in the blocks ``names``,
    summed, from ``r.blocks`` of a run's record (``record.Record``); None
    where the trace's count of steps is not what the run served, or the
    program's map names none of them."""
    served = r.traced_prefills if program == "prefill" \
        else len(r.traced_positions)
    ms = per_step_ms(r.blocks, program, served)
    if ms is None or not set(names) & set(ms):
        return None
    return sum(ms.get(b, 0.0) for b in names)


def launch_ms(red: dict | None, served: int) -> float | None:
    """Median over the traced decode steps of the host's time inside
    ``decode_dispatch``, in ms; None where there is no reduction or its
    count of dispatches is not ``served``."""
    if not red or not served or len(red["dispatch_s"]) != served:
        return None
    return 1e3 * statistics.median(red["dispatch_s"])
