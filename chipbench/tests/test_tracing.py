"""The reduction from trace events to busy time, idle time and the
breakdown."""
import pytest

from chipbench import tracing
from chipbench.tracing import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, line=tracing.OPS_LINE):
    return Event(plane, line, name, float(start), float(dur))


def test_union_clip_and_labels():
    events = [
        ev(HOST, tracing.WINDOW, 100, 1000, line="python"),
        ev(HOST, "prefill", 100, 300, line="python"),
        ev(HOST, "sample_sync", 400, 200, line="python"),
        ev(HOST, "decode_step", 600, 500, line="python"),
        # chip 0: a loop around two ops (140-360), one op crossing the
        # window's end; names are HLO text
        ev(DEV0, "%while.1 = (s32[]) while(%t), body=%b", 140, 220),
        ev(DEV0, "%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 150, 150),
        ev(DEV0, "%fusion.2 = bf16[8] fusion(%b)", 300, 50),
        ev(DEV0, "%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 700, 600),
        ev(DEV0, "ignored", 0, 5000, line="XLA Modules"),
        # chip 1: one op before the window (not counted)
        ev(DEV1, "fusion.3", 0, 50),
        ev(DEV1, "fusion.3", 200, 400),
    ]
    r = tracing.reduce(events)
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy 140-360 and 700-1100 = 620; chip 1 200-600 = 400
    assert r["busy_s"] == pytest.approx((620 + 400) / 2 * 1e-9)
    ops = dict(r["device_ops"])
    # the loop keeps only its own time: 140-150 and 350-360
    assert ops["while.1"] == pytest.approx(20e-9)
    assert ops["fusion.1"] == pytest.approx(550e-9)
    assert ops["fusion.2"] == pytest.approx(50e-9)
    assert ops["fusion.3"] == pytest.approx(400e-9)
    assert "ignored" not in ops
    gaps = dict(r["idle_gaps"])
    # chip 0: 100-140 prefill, 360-700: 40 prefill, 200 sample_sync,
    # 100 decode_step -> sample_sync; chip 1: 100-200 prefill,
    # 600-1100 decode_step
    assert gaps["prefill"] == pytest.approx(140e-9)
    assert gaps["sample_sync"] == pytest.approx(340e-9)
    assert gaps["decode_step"] == pytest.approx(500e-9)
    # step programs: ops by midpoint inside the host annotations; chip 0
    # prefill (100-400) holds the loop 140-360, decode_step (600-1100)
    # the op 700-1300 (midpoint 1000, counted whole); chip 1 prefill
    # holds nothing (fusion.3 200-600 has its midpoint in sample_sync)
    steps = r["steps"]
    assert steps["prefill"]["n"] == 1 and steps["decode_step"]["n"] == 1
    assert steps["prefill"]["busy_s"] == pytest.approx(220 / 2 * 1e-9)
    assert steps["decode_step"]["busy_s"] == pytest.approx(600 / 2 * 1e-9)

def test_union_of_overlapping_and_nested_intervals():
    assert tracing._union_s([0, 5, 2, 20, 21], [10, 6, 12, 30, 22]) == 22
    assert tracing._union_s([], []) == 0.0


def _record(trace, prefills, positions, m=None):
    from chipbench.record import Record
    from chipbench.reference import dense_gqa
    m = m or {"d": 64, "f": 160, "layers": 2, "heads": 4, "kv_heads": 2,
              "head_dim": 16, "vocab": 512, "tied": True, "qkv_bias": True}
    r = Record(arch=dense_gqa, dims=m,
               peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
               batch=2, prompt_len=8, setup_s=1.0)
    r.trace, r.traced_prefills, r.traced_positions = trace, prefills, \
        positions
    return r


def test_mfu_readers_divide_by_the_step_programs_device_time():
    from chipbench import catalog
    from chipbench.reference import dense_gqa as counts
    trace = {"busy_s": 1.0, "window_s": 2.0,
             "steps": {"prefill": {"n": 2, "busy_s": 0.5},
                       "decode_step": {"n": 3, "busy_s": 0.25}}}
    r = _record(trace, 2, [8, 9, 10])
    least = sum(counts.decode_roofline_s(r.dims, r.peaks, 2, p)
                for p in (8, 9, 10))
    assert catalog.reader("decode_mfu")(r) == pytest.approx(
        100 * least / 0.25)
    assert catalog.reader("prefill_mfu")(r) == pytest.approx(
        100 * 2 * counts.prefill_flops(r.dims, 2, 8) / (0.5 * 1e12))
    assert catalog.reader("device_idle_share")(r) == pytest.approx(50.0)
    # what the trace saw is not what the harness served: no reading
    assert catalog.reader("decode_mfu")(_record(trace, 2, [8, 9])) is None
    assert catalog.reader("prefill_mfu")(_record(trace, 1, [8])) is None
    assert catalog.reader("decode_mfu")(_record(None, 0, [])) is None


def test_nothing_to_read_gives_none():
    assert tracing.reduce([ev(HOST, tracing.WINDOW, 0, 10, "python")]) \
        is None
    assert tracing.reduce([ev(DEV0, "fusion", 0, 10)]) is None
