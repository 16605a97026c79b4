"""Training launcher.

CPU-friendly by default (reduced configs); pass --full to build the
published architecture sizes (requires a real TPU mesh).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 100 --d-model 128 --layers 2 --seq 128 --batch 8

Set ``REPRO_TRACE=/path/train.json`` to record every training step as
a span on the ``trainer`` track and dump a Chrome trace at exit (same
knob the kernel-conformance harness honors).
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.configs import TrainConfig, get_config, reduce_config
from repro.core.tpu_mapping import chip_for, serve_step_schedule, tpu_wcet
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import RunOptions
from repro.runtime.trainer import Trainer


def reduced_config(cfg, args):
    """CLI shim over configs.reduce_config (the shared shrink the
    serving autotuner keys its plans on)."""
    return reduce_config(cfg, layers=args.layers, d_model=args.d_model,
                         vocab=args.vocab)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true",
                    help="use the published architecture size")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="explicit per-step deadline; 0 = derive from "
                         "the WCET bound")
    ap.add_argument("--deadline-slack", type=float, default=50.0,
                    help="deadline = WCET bound x slack (the bound "
                         "targets the TPU mapping; on other backends "
                         "the slack absorbs the platform gap)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg, args)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=10,
                       total_steps=args.steps,
                       microbatch=args.microbatch)
    dcfg = DataConfig(vocab_size=cfg.vocab_size,
                      global_batch=args.batch, seq_len=args.seq)
    opts = RunOptions(chunk_q=64, chunk_kv=64, loss_chunk=64,
                      remat=False)

    trace_path = os.environ.get("REPRO_TRACE")
    rec = None
    if trace_path:
        from repro.obs import TraceRecorder
        rec = TraceRecorder(time_unit="us")

    # WCET-derived step deadline, same recipe as serving: the weight
    # pass over B*S tokens, tiled by the resolved kernel plan; the
    # forward+backward pass streams each weight ~3x (fwd, grad-wrt-
    # input, grad-wrt-weight), hence the 3x on the one-pass bound.
    from repro.models.lm import param_count
    from repro.tuning.model import ModelProblem, kernel_pins
    prob = ModelProblem(args.arch, args.batch * args.seq, args.seq,
                        1, layers=0 if args.full else args.layers,
                        d_model=args.d_model, vocab=args.vocab)
    chip = chip_for(jax.devices()[0])
    sched = serve_step_schedule(args.batch * args.seq, cfg.d_model,
                                param_count(cfg),
                                plan=kernel_pins(cfg, prob), chip=chip)
    wcet_s = 3.0 * tpu_wcet(sched, chip)
    deadline_s = (args.deadline_ms / 1e3 if args.deadline_ms > 0
                  else wcet_s * args.deadline_slack)
    from repro.resilience.deadline import DeadlineMonitor
    dmon = DeadlineMonitor(deadline_s=deadline_s, trace=rec)

    tr = Trainer(cfg, tcfg, dcfg, ckpt_dir=args.ckpt_dir, opts=opts,
                 trace=rec, deadline=dmon)
    hist = tr.run(args.steps)
    print(f"first loss {hist['loss'][0]:.4f} -> last "
          f"{hist['loss'][-1]:.4f} in {hist['wall_s'][0]:.1f}s")
    print(f"TPU-target WCET bound per step (fwd+bwd weight passes): "
          f"{wcet_s*1e3:.3f} ms")
    s = dmon.summary()
    print(f"deadline: {s['deadline_s']*1e3:.3f} ms/step  "
          f"overruns {s['overruns']}  ladder record/warn/shed "
          f"{s['n_record']}/{s['n_warn']}/{s['n_shed']}  "
          f"worst overrun {s['worst_overrun_s']*1e3:.3f} ms")

    if rec is not None and rec.spans:
        from repro.obs import write_chrome_trace
        write_chrome_trace(rec, trace_path)
        print(f"trace: {len(rec.spans)} spans -> {trace_path}")


if __name__ == "__main__":
    main()
