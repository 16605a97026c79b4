"""MultiVic -> TPU bridge: the paper's execution model instantiated on
the target hardware (v5e-class chip / pod constants from the
assignment).

Scale mapping (DESIGN.md §2):
    worker core + Vicuna      -> TPU core (MXU)
    data scratchpad           -> VMEM (software-managed, BlockSpec-tiled)
    management core + DMA     -> Pallas grid pipeline / XLA SPMD program
    DDR4                      -> HBM;  TileLink -> ICI collectives

`tpu_matmul_schedule` builds the same static Schedule IR the paper core
uses, but with TPU phase costs: HBM->VMEM tile DMAs double-buffered
against MXU tile compute; the per-phase WCET uses worst-case effective
bandwidths, giving a deterministic per-step latency bound — the
time-predictability claim carried to the datacenter target.  The
serving runtime (launch/serve.py) prints these bounds next to measured
step times.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from repro.core.schedule import DMA, Schedule, core_resource


@dataclass(frozen=True)
class TPUChip:
    peak_flops: float = 197e12       # bf16
    hbm_bw: float = 819e9            # bytes/s
    vmem_bytes: int = 128 * 1024 * 1024
    ici_bw: float = 50e9             # per link
    # worst-case derates for WCET (DMA contention, MXU pipeline bubbles)
    worst_hbm_derate: float = 0.8
    worst_mxu_eff: float = 0.85


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4 links.
V5E = TPUChip()

# Peak tables keyed by ``jax.Device.device_kind``.  A TPU whose kind is
# missing here is an error, never a silent v5e.
CHIPS = {"TPU v5 lite": V5E}


def chip_for(device) -> TPUChip:
    """The peak table a WCET bound is priced against on ``device``.

    On a TPU the entry for its ``device_kind`` (unknown kinds raise);
    on any other backend the bound stays the v5e *target*, as the CPU
    validation runs print it."""
    if device.platform != "tpu":
        return V5E
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for TPU kind {device.device_kind!r}; add it "
            f"to repro.core.tpu_mapping.CHIPS (have {sorted(CHIPS)})"
        ) from None


def tpu_matmul_schedule(m: int, k: int, n: int, *, n_devices: int = 1,
                        tile_m: int = 512, tile_n: int = 512,
                        elem_bytes: int = 2,
                        chip: TPUChip = V5E) -> Schedule:
    """B-stationary blocked matmul on one or more TPU 'workers'.

    N is partitioned across devices (the paper's B-column blocks);
    within a device, (tile_m x k) A-tiles stream HBM->VMEM double-
    buffered against MXU compute, C tiles stream back — the identical
    dataflow to the paper's §4.3 at a 10^4x bandwidth scale.
    """
    assert n % n_devices == 0
    n_local = n // n_devices
    tiles_m = math.ceil(m / tile_m)
    tiles_n = math.ceil(n_local / tile_n)
    vmem_need = (k * tile_n + 2 * tile_m * k + 2 * tile_m * tile_n) \
        * elem_bytes
    sched = Schedule(meta={"kind": "tpu_matmul", "m": m, "k": k, "n": n,
                           "n_devices": n_devices, "tile_m": tile_m,
                           "tile_n": tile_n, "vmem_need": vmem_need,
                           "vmem_ok": vmem_need <= chip.vmem_bytes})
    for dev in range(n_devices):
        prev_comp = None
        for tn in range(tiles_n):
            b_load = sched.add(
                kind="dma_load", resource=DMA,
                bytes_moved=k * tile_n * elem_bytes, spm_core=dev,
                deps=(prev_comp,) if prev_comp is not None else (),
                tag=f"B[{tn}]->dev{dev}")
            for tm in range(tiles_m):
                a_load = sched.add(
                    kind="dma_load", resource=DMA,
                    bytes_moved=tile_m * k * elem_bytes,
                    deps=(b_load,), spm_core=dev,
                    tag=f"A[{tm}]->dev{dev}")
                comp = sched.add(
                    kind="compute", resource=core_resource(dev),
                    deps=(a_load,) + ((prev_comp,) if prev_comp else ()),
                    macs=tile_m * k * tile_n,
                    elems=tile_m * tile_n, spm_core=dev,
                    tag=f"C[{tm},{tn}]@dev{dev}")
                sched.add(
                    kind="dma_store", resource=DMA,
                    bytes_moved=tile_m * tile_n * elem_bytes,
                    deps=(comp,), spm_core=dev, tag=f"C[{tm},{tn}]->hbm")
                prev_comp = comp
    sched.validate_dag()
    sched.validate_interference_freedom()
    return sched


def serve_step_schedule(batch: int, d_model: int, n_params: int, *,
                        plan: dict, elem_bytes: int = 2,
                        chip: TPUChip = V5E) -> Schedule:
    """Static schedule for one decode step's weight pass, tiled by the
    SERVED plan.

    The serving runtime resolves a model plan (tuning.model) whose
    ``mm_bm``/``mm_bn`` pins are the decode matmul tiles; building the
    WCET schedule from those same pins is what makes the printed bound
    (and the deadline derived from it) track the plan actually served
    instead of a hand-picked constant.  Each generated token multiplies
    the [batch, d_model] activations against every weight matrix once:
    an effective [batch, d_model, 2*n_params/d_model] matmul.
    """
    n_eff = max(d_model, 2 * n_params // d_model)
    tile_m = max(1, min(int(plan["mm_bm"]), batch))
    tile_n = max(1, min(int(plan["mm_bn"]), n_eff))
    return tpu_matmul_schedule(batch, d_model, n_eff, tile_m=tile_m,
                               tile_n=tile_n, elem_bytes=elem_bytes,
                               chip=chip)


def tpu_phase_wcet(ph, chip: TPUChip = V5E) -> float:
    """Worst-case seconds for one TPU phase."""
    if ph.kind == "compute":
        return 2.0 * ph.macs / (chip.peak_flops * chip.worst_mxu_eff)
    return ph.bytes_moved / (chip.hbm_bw * chip.worst_hbm_derate)


def tpu_wcet(sched: Schedule, chip: TPUChip = V5E) -> float:
    """Compositional bound: serialized-DMA + slowest-core chain (the
    closed form from core/wcet.py with TPU phase costs)."""
    dma_total = sum(tpu_phase_wcet(p, chip) for p in sched.phases
                    if p.kind != "compute")
    per_core = {}
    for p in sched.phases:
        if p.kind == "compute":
            per_core[p.resource] = per_core.get(p.resource, 0.0) \
                + tpu_phase_wcet(p, chip)
    return dma_total + (max(per_core.values()) if per_core else 0.0)


def tpu_steady_state(sched: Schedule, chip: TPUChip = V5E) -> float:
    """Overlap-aware estimate: max(total DMA, slowest core compute) —
    what double buffering achieves when one side dominates."""
    dma_total = sum(tpu_phase_wcet(p, chip) for p in sched.phases
                    if p.kind != "compute")
    per_core = {}
    for p in sched.phases:
        if p.kind == "compute":
            per_core[p.resource] = per_core.get(p.resource, 0.0) \
                + tpu_phase_wcet(p, chip)
    comp = max(per_core.values()) if per_core else 0.0
    return max(dma_total, comp)
