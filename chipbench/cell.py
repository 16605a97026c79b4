"""One run of one cell: set-up, the measured window, an optional traced
segment, the metrics, and the comparison with the reference."""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import catalog, check, loop, model, tracing
from chipbench import weights as W
from chipbench.peaks import peaks_for
from chipbench.record import Record, window_record
from chipbench.reference.dense_gqa import Reference

TRACE_S = 2.0        # length of the traced segment after the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    pass


def devices(chips: int) -> list:
    """The cell's TPU chips; raises ``NoChip`` on anything else."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def prompt_source(traffic: dict, vocab: int, seed: int):
    """Each call: the next batch's prompts, token ids uniform over the
    vocabulary, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    shape = (traffic["batch"], traffic["prompt_len"])
    return lambda: rng.integers(0, vocab, shape, dtype=np.int32)


def build_server(cfg, m: dict, traffic: dict, seed: int, log=print):
    """The program's serving plan, weights and compiled steps for the
    cell's shapes (as ``serve.run`` resolves and compiles them)."""
    from repro.launch import serve
    from repro.models.lm import RunOptions
    from repro.tuning.model import ModelProblem, resolve_model_plan
    B, P, G = traffic["batch"], traffic["prompt_len"], traffic["gen"]
    resolved = resolve_model_plan(
        cfg, ModelProblem(cfg.name, B, P, G, layers=0),
        {"chunk_q": None, "chunk_kv": None})
    plan = resolved["plan"]
    log(f"serving plan [{resolved['source']}]: {plan}")
    opts = RunOptions(chunk_q=int(plan["chunk_q"]),
                      chunk_kv=int(plan["chunk_kv"]), cache_len=P + G,
                      remat=False, decode_scan=bool(plan["decode_scan"]))
    t0 = time.monotonic()
    params = jax.block_until_ready(model.make_params(cfg, m, seed))
    t1 = time.monotonic()
    prompt = {"tokens": jnp.zeros((B, P), jnp.int32)}
    prefill, step, compile_s = serve.compile_step_fns(cfg, params, prompt,
                                                      opts, P)
    log(f"set-up s: weights {t1 - t0} compile/load prefill "
        f"{compile_s['prefill']} decode {compile_s['decode']}")
    V = cfg.vocab_size
    return loop.Server(params=params, prefill=prefill, step=step,
                       sample=lambda logits: jnp.argmax(logits[:, :V], -1),
                       prompt_len=P, gen=G)


class GcWatch:
    """Python's garbage collections while ``on``: count and longest."""

    def __init__(self):
        self.on, self.n, self.longest, self._t = False, 0, 0.0, 0.0
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.n += 1
            self.longest = max(self.longest, time.monotonic() - self._t)


class CompileCounter:
    """Counts tracing and compilation events while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool,
             t_proc: float, require_tpu: bool = True,
             server_hook=None, control: str | None = None) -> dict:
    """Everything one run does; returns the result line's object.
    ``server_hook(server)`` may replace parts of the timed path (tests).
    ``control`` (``"fp8"`` or ``"int8"``) puts the reference computed at
    that lower precision in the program's place for the comparison: the
    tokens judged are the ones the control puts first at each position
    of the served requests (``tools/readings.py``, tests)."""
    def log(msg):
        print(msg, flush=True)

    c, traffic = cell.config, cell.traffic
    devs = devices(cell.chips) if require_tpu else jax.devices()[:1]
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_tpu \
        else peaks_for("TPU v5 lite")
    m = W.dims(c)
    cfg = model.program_config(c)
    log(f"cell {cell.name}: {c['name']} layers {m['layers']} d {m['d']} "
        f"vocab {m['vocab']}; batch {traffic['batch']} prompt "
        f"{traffic['prompt_len']} gen {traffic['gen']}; seed {seed}; "
        f"device {dev.device_kind} x{len(devs)}")
    server = build_server(cfg, m, traffic, seed, log)
    if server_hook is not None:
        server_hook(server)
    # warm-up: every shape and host-side op the window will use
    warm = loop.Batch(server, np.zeros((traffic["batch"],
                                        traffic["prompt_len"]), np.int32))
    warm.start()
    warm.step()
    warm.step()
    del warm
    prompts = prompt_source(traffic, m["vocab"], seed)
    counter, gcw = CompileCounter(), GcWatch()
    # what set-up made lives as long as the process: a full collection
    # would walk all of it (about 0.1 s) at some step of the window
    gc.collect()
    gc.freeze()

    t_start = time.monotonic()
    rec = Record(dims=m, peaks=peaks, batch=traffic["batch"],
                 prompt_len=traffic["prompt_len"], setup_s=t_start - t_proc)
    counter.on = gcw.on = True
    batches: list = []
    inflight = loop.serve(server, prompts, t_start + seconds, log=batches)
    t_close = inflight.times[-1]
    counter.on = gcw.on = False
    gc.callbacks.remove(gcw)
    window_record(rec, batches, t_start, t_close)
    log(f"window: {rec.window_s} s, {len(batches)} batches, "
        f"{rec.tokens} tokens, {len(rec.steps)} decode steps, "
        f"{counter.n} compilations inside; {gcw.n} garbage collections, "
        f"longest {gcw.longest} s; slowest steps (position, s) "
        f"{sorted(rec.steps, key=lambda p: -p[1])[:5]}; prefills (s) "
        f"{rec.prefill_s[:8]}")

    if trace:
        inflight = traced_segment(server, prompts, inflight, batches, rec)
        log(f"trace: {rec.trace}; served {rec.traced_prefills} prefills, "
            f"{len(rec.traced_positions)} decode steps")
    gc.unfreeze()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak}")

    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        v = catalog.reader(spec["name"])(rec)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}

    # correctness: finish the batch in flight if the window finished none
    if not any(b.done for b in batches):
        while not inflight.done:
            inflight.step()
    attempted = traffic["batch"] * sum(1 for b in batches
                                       if b.times and b.times[0] <= t_close)
    failed = check.out_of_vocab(batches, m["vocab"])
    picks = check.sample(batches, traffic["check_requests"],
                         check.check_rng(seed))
    seqs, served = check.sequences(picks)
    # free the program's state before the reference runs on the chip
    del picks, inflight, batches
    server.params = server.prefill = server.step = None
    del server
    gc.collect()
    t_ref = time.monotonic()
    gaps = Reference(m).gaps(seed, jnp.asarray(seqs), jnp.asarray(served),
                             (control,) if control else ())
    readings = {k: float(np.max(v)) for k, v in gaps.items()}
    token_gap = readings[control or "program"]
    limit = float(cell.limits["token_gap"])
    log(f"reference: {seqs.shape[0]} requests, {served.size} served "
        f"tokens, {time.monotonic() - t_ref} s")
    checks = {"token_gap": {"value": token_gap, "limit": limit}}
    correct = bool(np.isfinite(token_gap) and token_gap <= limit
                   and failed == 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace and rec.trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and rec.trace:
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    if control:
        out["readings"] = readings
    for name, v in checks.items():
        print(f"check {name}: {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(f"check failed_requests: {failed} limit 0", file=sys.stderr,
          flush=True)
    checks["failed_requests"] = {"value": failed, "limit": 0}
    out["checks"] = checks
    return out


def traced_segment(server, prompts, inflight, batches, rec: Record):
    """Serve on for ``TRACE_S`` under the profiler; fills ``rec.trace``
    with the trace's reduction and ``rec.traced_*`` with the prefills
    and decode positions served, and returns the batch then in flight."""
    P = server.prompt_len
    going = inflight if inflight is not None and not inflight.done else None
    k0, n0 = (going.steps if going else 0), len(batches)
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(log_dir)
        with loop.annotate(tracing.WINDOW):
            inflight = loop.serve(server, prompts,
                                  time.monotonic() + TRACE_S, inflight,
                                  batches)
        jax.profiler.stop_trace()
        rec.trace = tracing.reduce(tracing.load(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    if going:
        rec.traced_positions.extend(range(P + k0, P + going.steps))
    for b in batches[n0:]:
        rec.traced_prefills += 1
        rec.traced_positions.extend(range(P, P + b.steps))
    return inflight
