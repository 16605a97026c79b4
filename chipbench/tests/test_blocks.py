"""The reduction from trace events to the step programs' device time by
block and to the decode step's launch, on events made by hand as in
``test_tracing.py``; and that the new host spans leave ``tracing`` and
the metrics it feeds as they were."""
import pytest

from chipbench import blocks, tracing
from chipbench.tracing import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, line=tracing.OPS_LINE):
    return Event(plane, line, name, float(start), float(dur))


def host(name, start, dur):
    return ev(HOST, name, start, dur, line="python")


# a prefill (100-400) and two decode steps (600-800, 900-1100), each
# dispatched inside its host annotation; ops are HLO text as on the chip
EVENTS = [
    host(tracing.WINDOW, 100, 1000),
    host("prefill", 100, 300),
    host("prefill_dispatch", 110, 20),
    host("sample_sync", 400, 200),
    host("decode_step", 600, 200),
    host("decode_dispatch", 600, 40),
    host("decode_step", 900, 200),
    host("decode_dispatch", 900, 10),
    # chip 0: prefill is a loop (140-360) around an attention op and an
    # op that no block names
    ev(DEV0, "%while.1 = (s32[]) while(%t), body=%b", 140, 220),
    ev(DEV0, "%fusion.1 = bf16[8] fusion(%a), kind=kLoop", 150, 150),
    ev(DEV0, "%copy.7 = bf16[8] copy(%b)", 300, 50),
    # decode: a matmul and the head in each step, starting 30 and 5
    # after the dispatch began
    ev(DEV0, "%fusion.2 = bf16[8] fusion(%a)", 630, 100),
    ev(DEV0, "%fusion.3 = bf16[8] fusion(%b)", 730, 40),
    ev(DEV0, "%fusion.2 = bf16[8] fusion(%a)", 905, 100),
    ev(DEV0, "%fusion.3 = bf16[8] fusion(%b)", 1005, 40),
    # chip 1: the same decode steps, no prefill
    ev(DEV1, "fusion.2", 620, 100),
    ev(DEV1, "fusion.3", 720, 40),
    ev(DEV1, "fusion.2", 905, 100),
    ev(DEV1, "fusion.3", 1005, 40),
]
MAPS = {"prefill": {"fusion.1": "attn_core"},
        "decode_step": {"fusion.2": "ffn", "fusion.3": "head",
                        "fusion.1": "attn_core"}}


def test_blocks_sum_to_the_step_programs_device_time():
    red = blocks.reduce(EVENTS, MAPS)
    tr = tracing.reduce(EVENTS)
    for k in tracing.STEPS:
        s = red["steps"][k]
        assert s["n"] == tr["steps"][k]["n"]
        assert s["busy_s"] == pytest.approx(tr["steps"][k]["busy_s"])
        assert sum(s["blocks"].values()) == pytest.approx(s["busy_s"])
    pre = red["steps"]["prefill"]["blocks"]
    # the loop keeps only its own time (140-150, 350-360) and goes to
    # other with the unnamed copy; chip 1 ran no prefill
    assert pre["attn_core"] == pytest.approx(150 / 2 * 1e-9)
    assert pre["other"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert red["steps"]["prefill"]["other_ops"] == pytest.approx(
        {"while.1": 10e-9, "copy.7": 25e-9})
    dec = red["steps"]["decode_step"]["blocks"]
    assert dec["ffn"] == pytest.approx(400 / 2 * 1e-9)
    assert dec["head"] == pytest.approx(160 / 2 * 1e-9)
    # every block the map names is read, with no time where it has none
    assert set(dec) == {"ffn", "head", "attn_core", "other"}
    assert dec["attn_core"] == dec["other"] == 0


def test_an_operation_no_map_knows_goes_to_other():
    red = blocks.reduce(EVENTS, {"prefill": {}, "decode_step": {}})
    for k in tracing.STEPS:
        s = red["steps"][k]
        assert s["blocks"]["other"] == pytest.approx(s["busy_s"])
        assert sum(s["other_ops"].values()) == pytest.approx(s["busy_s"])


def test_decode_dispatch_is_read_on_the_hosts_clock():
    red = blocks.reduce(EVENTS, MAPS)
    assert red["dispatch_s"] == pytest.approx([40e-9, 10e-9])
    assert blocks.launch_ms(red, 2) == pytest.approx(25e-6)
    # the device's clock may be offset from the host's: moving every
    # device operation changes no dispatch time
    early = [e._replace(start_ns=e.start_ns - 30) if e.plane != HOST else e
             for e in EVENTS]
    assert blocks.reduce(early, MAPS)["dispatch_s"] == red["dispatch_s"]


def test_readings_per_step_and_none_on_a_count_mismatch():
    red = blocks.reduce(EVENTS, MAPS)
    ms = blocks.per_step_ms(red, "decode_step", 2)
    assert set(ms) == set(MAPS["decode_step"].values()) | {blocks.OTHER}
    assert ms["ffn"] == pytest.approx(1e3 * 200e-9 / 2)
    assert blocks.per_step_ms(red, "prefill", 1)["attn_core"] == \
        pytest.approx(1e3 * 75e-9)
    # what the trace saw is not what the harness served: no reading
    assert blocks.per_step_ms(red, "decode_step", 3) is None
    assert blocks.per_step_ms(red, "prefill", 2) is None
    assert blocks.launch_ms(red, 1) is None
    assert blocks.per_step_ms(None, "decode_step", 2) is None
    assert blocks.launch_ms(None, 2) is None
    # a program with no dispatch spans (one that predates them)
    old = [e for e in EVENTS if e.name not in blocks.DISPATCH]
    assert blocks.launch_ms(blocks.reduce(old, MAPS), 2) is None


def test_nothing_to_read_gives_none():
    assert blocks.reduce(EVENTS, None) is None
    assert blocks.reduce([host(tracing.WINDOW, 0, 10)], MAPS) is None
    assert blocks.reduce([ev(DEV0, "fusion", 0, 10)], MAPS) is None


def test_dispatch_spans_leave_tracing_and_its_metrics_unchanged():
    """The dispatch spans nest inside the step annotations; the outside-in
    reduction and its readers give what they gave without them."""
    from chipbench.tests.test_tracing import _record
    from chipbench import catalog
    old = [e for e in EVENTS if e.name not in blocks.DISPATCH]
    new, was = tracing.reduce(EVENTS), tracing.reduce(old)
    assert new == was
    assert new["steps"]["prefill"]["busy_s"] == pytest.approx(220 / 2e9)
    assert new["steps"]["decode_step"]["busy_s"] == pytest.approx(
        (280 + 280) / 2e9)
    for name, prefills, positions in (("decode_mfu", 1, [8, 9]),
                                      ("prefill_mfu", 1, [8, 9]),
                                      ("device_idle_share", 1, [8, 9])):
        read = catalog.reader(name)
        got = read(_record(new, prefills, positions))
        assert got is not None
        assert got == read(_record(was, prefills, positions))


def test_block_names_are_the_programs():
    from repro.obs import BLOCKS
    assert set(blocks.MATMUL) == set(BLOCKS) - {"attn_core"}
    assert blocks.OTHER not in BLOCKS


def test_a_block_no_one_named_before_is_summed_under_its_name():
    """A block a later program adds (a state-space layer's ``ssm``) is
    read under its own name, not raised on and not put in ``other``."""
    maps = {k: dict(v) for k, v in MAPS.items()}
    maps["decode_step"]["fusion.2"] = "ssm"
    red = blocks.reduce(EVENTS, maps)
    dec = red["steps"]["decode_step"]
    assert dec["blocks"]["ssm"] == pytest.approx(400 / 2 * 1e-9)
    assert dec["blocks"]["other"] == 0 and "fusion.2" not in dec["other_ops"]
    assert sum(dec["blocks"].values()) == pytest.approx(dec["busy_s"])
    assert blocks.per_step_ms(red, "decode_step", 2)["ssm"] == \
        pytest.approx(1e3 * 200e-9 / 2)


@pytest.mark.parametrize("program,served", [("decode_step", (1, [8, 9])),
                                            ("prefill", (1, [8, 9]))])
def test_block_metrics_sum_to_the_step_programs_device_time(program,
                                                             served):
    """The three block metrics of a step program read its ms per traced
    step by block from a run's record, and together they are the
    program's device time as ``tracing.reduce`` gives it."""
    from chipbench import catalog
    from chipbench.tests.test_tracing import _record
    r = _record(tracing.reduce(EVENTS), *served)
    r.blocks = blocks.reduce(EVENTS, dict(MAPS, prefill={
        "fusion.1": "attn_core", "copy.7": "ffn"}))
    n = len(r.traced_positions) if program == "decode_step" \
        else r.traced_prefills
    prefix = "decode" if program == "decode_step" else "prefill"
    got = {b: catalog.reader(f"{prefix}_{b}_ms")(r)
           for b in ("attn_core", "matmul", "other")}
    ms = blocks.per_step_ms(r.blocks, program, n)
    assert got["attn_core"] == ms["attn_core"]
    assert got["other"] == ms["other"]
    assert got["matmul"] == pytest.approx(sum(ms.get(b, 0.0)
                                              for b in blocks.MATMUL))
    busy_ms = 1e3 * r.trace["steps"][program]["busy_s"] / n
    assert sum(got.values()) == pytest.approx(busy_ms, rel=1e-9)
    # a count the trace does not match, or no reduction: no reading
    r.traced_prefills, r.traced_positions = 2, [8, 9, 10]
    assert all(catalog.reader(f"{prefix}_{b}_ms")(r) is None
               for b in ("attn_core", "matmul", "other"))
    r.blocks = None
    assert catalog.reader(f"{prefix}_other_ms")(r) is None


def test_maps_need_the_compiled_programs():
    from chipbench.loop import Server
    plain = Server(params={}, prefill=lambda p, b: None,
                   step=lambda p, c, t, i: None, sample=None,
                   prompt_len=1, gen=1)
    assert blocks.maps(plain) is None


def test_load_reads_the_dispatch_spans_of_a_real_trace(tmp_path):
    """A profiler trace on the CPU holds the host annotations; ``load``
    keeps the window, the steps and the dispatches, and nothing else of
    the host."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    annotate = jax.profiler.TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with annotate(tracing.WINDOW):
            for _ in range(2):
                with annotate("decode_step"):
                    with annotate("decode_dispatch"):
                        y = f(jnp.ones(4))
                    y.block_until_ready()
                with annotate("sample_sync"):
                    y.block_until_ready()
    names = sorted(e.name for e in blocks.load(str(tmp_path))
                   if "/host:" in e.plane)
    assert names == ["decode_dispatch"] * 2 + ["decode_step"] * 2 + \
        [tracing.WINDOW]
