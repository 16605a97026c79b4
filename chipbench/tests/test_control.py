"""The lower-precision control through a whole run, at a size a test run
can hold: ``run_cell`` with the reference computed with every matrix
rounded to fp8 (4 exponent and 3 mantissa bits, one scale per output
channel) in the program's place must come out not correct, where the
program's own reading in the same run lies inside the limit."""
import json
import pathlib
import time

import pytest

from chipbench import catalog, cell

DATA = pathlib.Path(__file__).resolve().parent / "data"
LIMITS = pathlib.Path(__file__).resolve().parents[1] / "limits"
STANDS_FOR = {"tiny-qwen2": ("qwen2-0.5b.decode", "qwen2-0.5b.prefill"),
              "tiny-llama": ("deepseek-67b-l4.decode",
                             "deepseek-67b-l4.prefill")}
TRAFFIC = {"batch": 8, "prompt_len": 64, "gen": 24, "check_requests": 8}


@pytest.mark.parametrize("seed", [2**33 + 1, 2**33 + 2, 2**33 + 3])
@pytest.mark.parametrize("name", sorted(STANDS_FOR))
def test_fp8_control_fails_where_the_program_passes(name, seed):
    c = json.loads((DATA / f"{name}.json").read_text())
    limits = [json.loads((LIMITS / f"{w}.json").read_text())["token_gap"]
              for w in STANDS_FOR[name]]
    # held to the loosest of the limits: the control must fail them all
    one = catalog.Cell(name=name, chips=1, config=c, traffic=TRAFFIC,
                       limits={"token_gap": max(limits)}, end_to_end=[],
                       per_layer=[])
    out = cell.run_cell(one, seed, 0.5, False, time.monotonic(),
                        require_tpu=False, control="fp8")
    assert out["readings"]["program"] <= min(limits)
    assert out["checks"]["token_gap"]["value"] == out["readings"]["fp8"]
    assert not out["correct"], out["checks"]
