"""The Zamba2 architecture's module (``reference/zamba2.py``) at test size
on the CPU: the program's prefill and decode through the cache against
the reference's full forward, a whole run of a tiny Zamba2 cell through
``run_cell``, the fp8 control, the counts at the published widths, and
the state-space blocks' metrics."""
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import blocks, catalog, cell, tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]
TRAFFIC = {"batch": 8, "prompt_len": 64, "gen": 24, "check_requests": 8}
LIMIT = json.loads((ROOT / "chipbench/limits/zamba2-7b-l21.decode.json")
                   .read_text())["token_gap"]


def arch(name="tiny-zamba2", where=DATA):
    path = where / f"{name}.json"
    c = json.loads(path.read_text())
    a = catalog.architecture(c, path)
    return c, a, a.dims(c)


def test_prefill_then_decode_through_the_cache_is_the_reference():
    """The program in float32 (its bf16 weights widened) prefills 32
    tokens, then decodes 7 through its cache (K/V of the hybrid layers,
    convolution and ssm states); every logit it gives is the reference's
    at that position, which runs the recurrence token by token.  The
    tolerance is float32 rounding in two orders of summation."""
    from repro.models import lm
    c, a, m = arch()
    cfg = a.program_config(c)
    seed = 2**34 + 3
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          a.make_params(cfg, m, seed))
    f32 = dataclasses.replace(cfg, dtype="float32")
    S, E = 32, 8
    toks = jax.random.randint(jax.random.PRNGKey(0), (3, S + E), 0,
                              m["vocab"]).astype(jnp.int32)
    opts = lm.RunOptions(chunk_q=8, chunk_kv=8, cache_len=S + E,
                         remat=False)
    lg, cache = lm.prefill(f32, params, {"tokens": toks[:, :S]}, opts)
    got = [lg]
    for t in range(E - 1):
        lg, cache = lm.decode_step(f32, params, cache, toks[:, S + t],
                                   S + t, opts)
        got.append(lg)
    got = jnp.stack(got, 1)[..., :m["vocab"]]
    ref = a.Reference(m).logits(seed, toks[:, :S + E - 1])[:, S - 1:]
    assert float(jnp.max(jnp.abs(got - ref)) / jnp.std(ref)) < 1e-4


def _cell(limit):
    c, a, _ = arch()
    return catalog.Cell(name="tiny-zamba2", chips=1, config=c, arch=a,
                        traffic=TRAFFIC, limits={"token_gap": limit},
                        end_to_end=[], per_layer=[])


@pytest.mark.parametrize("seed", [2**33 + 1, 2**33 + 2, 2**33 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """A whole run of a tiny Zamba2 cell through the harness: the bf16
    program's widest gap lies inside the cell's limit, and the fp8
    control put in its place makes the run not correct."""
    out = cell.run_cell(_cell(LIMIT), seed, 0.5, False, time.monotonic(),
                        require_tpu=False, control="fp8")
    assert out["readings"]["program"] <= LIMIT
    assert out["checks"]["token_gap"]["value"] == out["readings"]["fp8"]
    assert not out["correct"], out["checks"]


def test_a_tiny_zamba2_cell_is_correct_through_run_cell():
    out = cell.run_cell(_cell(LIMIT), 2**36 + 11, 0.5, False,
                        time.monotonic(), require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_counts_at_the_published_widths():
    """The cell's sizes as the configuration states them: 2.48 B
    parameters, 28,672 bytes of K/V a token in each of the three hybrid
    layers, 962,048 bytes of state a row in each layer."""
    c, a, m = arch("zamba2-7b-l21", ROOT / "chipbench" / "configs")
    assert m["hybrid"] == (6, 11, 17) and m["layers"] == 21
    assert a.mamba_matmul_params(m) == 3584 * 14704 + 7168 * 3584
    assert a.weight_bytes(m) == 4962043968
    assert a.kv_bytes_per_token(m) == 3 * 28672
    assert a.state_bytes(m, 1) == 21 * 962048
    assert a.ssm_core_bytes(m, 64) == 2 * 21 * 64 * 962048
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # bandwidth-bound: the bytes of a step over 819 GB/s
    assert a.decode_roofline_s(m, peaks, 64, 1023) == pytest.approx(
        a.decode_bytes(m, 64, 1023) / 819e9)
    assert a.decode_bytes(m, 64, 1023) == pytest.approx(13.19e9, rel=1e-3)
    assert a.prefill_flops(m, 1, 1) == a.decode_flops(m, 1, 0)


def test_the_program_must_run_what_the_file_says():
    c, a, _ = arch()
    a.program_config(c)
    for key, value in (("hidden_act", "silu"), ("use_mem_rope", False),
                       ("attention_hidden_size", 64)):
        with pytest.raises(ValueError):
            a.program_config(dict(c, **{key: value}))


# -- the state-space blocks' metrics --------------------------------------

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=tracing.OPS_LINE):
    return tracing.Event(plane, line, name, float(start), float(dur))


# two decode steps, each a projection, a state update, an attention, an
# MLP and an op no block names
EVENTS = [ev(HOST, tracing.WINDOW, 0, 1000, "python")]
for t0 in (100, 500):
    EVENTS += [ev(HOST, "decode_step", t0, 300, "python"),
               ev(DEV, "fusion.1", t0 + 10, 40),
               ev(DEV, "fusion.2", t0 + 50, 100),
               ev(DEV, "fusion.3", t0 + 150, 60),
               ev(DEV, "fusion.5", t0 + 210, 30),
               ev(DEV, "copy.4", t0 + 240, 20)]
MAPS = {"prefill": {}, "decode_step": {"fusion.1": "ssm_proj",
                                       "fusion.2": "ssm_core",
                                       "fusion.3": "attn_core",
                                       "fusion.5": "ffn"}}


def _record(m, a):
    from chipbench.record import Record
    r = Record(arch=a, dims=m, peaks={"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9},
               batch=64, prompt_len=512, setup_s=1.0)
    r.trace = tracing.reduce(EVENTS)
    r.blocks = blocks.reduce(EVENTS, MAPS)
    r.traced_positions = [512, 513]
    return r


def test_state_space_blocks_sum_with_the_others_to_the_device_time():
    """``ssm_proj`` and ``ssm_core`` are read under their names; with the
    accepted block metrics they add up to the decode program's device
    time as ``tracing.reduce`` gives it."""
    _, a, m = arch("zamba2-7b-l21", ROOT / "chipbench" / "configs")
    r = _record(m, a)
    read = {n: catalog.reader(n)(r) for n in (
        "decode_ssm_proj_ms", "decode_ssm_core_ms", "decode_attn_core_ms",
        "decode_matmul_ms", "decode_other_ms")}
    assert read["decode_ssm_proj_ms"] == pytest.approx(40e-6)
    assert read["decode_ssm_core_ms"] == pytest.approx(100e-6)
    assert read["decode_other_ms"] == pytest.approx(20e-6)
    assert read["decode_matmul_ms"] == pytest.approx(30e-6)
    busy_ms = 1e3 * r.trace["steps"]["decode_step"]["busy_s"] / 2
    assert sum(read.values()) == pytest.approx(busy_ms, rel=1e-9)


def test_ssm_core_roofline_reads_the_states_bytes_over_the_block_time():
    _, a, m = arch("zamba2-7b-l21", ROOT / "chipbench" / "configs")
    r = _record(m, a)
    got = catalog.reader("decode_ssm_core_roofline")(r)
    least_ms = 1e3 * a.ssm_core_bytes(m, 64) / 819e9
    assert got == pytest.approx(100 * least_ms / 100e-6)
    # an architecture with no states, or a trace with no ssm_core: none
    from chipbench.reference import dense_gqa
    r.arch = dense_gqa
    assert catalog.reader("decode_ssm_core_roofline")(r) is None
    r = _record(m, a)
    r.blocks = blocks.reduce(EVENTS, {"prefill": {}, "decode_step": {}})
    assert catalog.reader("decode_ssm_core_roofline")(r) is None
    assert catalog.reader("decode_ssm_core_ms")(r) is None
