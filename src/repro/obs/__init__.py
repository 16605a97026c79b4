"""Predictability observatory.

The paper's headline claim is *low execution-time fluctuation*, not raw
speed (§5.1).  ``repro.core`` can simulate jitter and bound it; this
package makes it observable:

- ``trace``        — :class:`TraceRecorder`: lightweight span/counter
  recorder shared by the cycle-accurate simulator (explicit cycle
  timestamps) and the wall-clock paths (trainer step loop, kernel
  conformance harness).
- ``chrome_trace`` — export a recorder to the Chrome trace-event JSON
  format (load in ``chrome://tracing`` / Perfetto).
- ``jitter``       — the paper's fluctuation metrics (mean, p99,
  max−min spread, coefficient of variation, WCET margin) over seeded
  simulator sweeps.
- ``report``       — schema-versioned structured sink for
  ``benchmarks/run.py --json`` so the BENCH trajectory is machine-
  readable instead of print-only CSV.
- ``blocks``       — the block names the model code scopes the served
  step programs with, and the map from a compiled program's HLO
  operations to them (what a device trace's operation time is summed
  by).
"""
from repro.obs.blocks import BLOCKS, SSM_BLOCKS, op_blocks, op_names
from repro.obs.chrome_trace import to_chrome_trace, write_chrome_trace
from repro.obs.jitter import JitterStats, jitter_stats, simulate_sweep
from repro.obs.report import (BENCH_SCHEMA_VERSION, hw_fingerprint,
                              make_report, validate_report)
from repro.obs.trace import Counter, Instant, Span, TraceRecorder

__all__ = [
    "BENCH_SCHEMA_VERSION",
    "BLOCKS",
    "SSM_BLOCKS",
    "Counter",
    "Instant",
    "JitterStats",
    "Span",
    "TraceRecorder",
    "hw_fingerprint",
    "jitter_stats",
    "make_report",
    "op_blocks",
    "op_names",
    "simulate_sweep",
    "to_chrome_trace",
    "validate_report",
    "write_chrome_trace",
]
