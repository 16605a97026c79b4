"""Every piece of every cell is found by its name, and BENCHMARK.json
keeps to the shape the harness and its checker read."""
import json
import pathlib
import re
import shutil

import pytest

from chipbench import catalog, model
from chipbench import weights as W

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name(workload):
    cell = catalog.cell(workload, ROOT)
    c = cell.config
    model.program_config(c)          # the program runs what the file says
    for k in ("batch", "prompt_len", "gen", "check_requests"):
        assert cell.traffic[k] > 0
    assert 0 < cell.limits["token_gap"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "tokens_per_s"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(catalog.reader(m["name"], ROOT))


def test_entries_keep_to_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        for k in c["reduced"]:
            assert k in f and k in f.get("published", {}), k
            assert not k.endswith(("_dim", "_rank", "_size")), k
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["layer"] in layers


def test_a_new_cell_is_data_files_alone(tmp_path):
    """A cell added by files of its own is found without editing any."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "qwen2-0.5b.tiny", "config":
                               "qwen2-0.5b", "traffic": "tiny", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"batch": 2, "prompt_len": 8, "gen": 4, "check_requests": 2}))
    (root / "chipbench" / "limits" / "qwen2-0.5b.tiny.json").write_text(
        json.dumps({"token_gap": 0.5}))
    (root / "chipbench" / "metrics" / "a_new_metric.py").write_text(
        "def read(r):\n    return 1.0\n")
    cell = catalog.cell("qwen2-0.5b.tiny", root)
    assert cell.traffic["batch"] == 2 and cell.limits["token_gap"] == 0.5
    assert catalog.reader("a_new_metric", root)(None) == 1.0


def test_configuration_files_state_the_published_widths():
    q = W.dims(json.loads((ROOT / "chipbench/configs/qwen2-0.5b.json")
                          .read_text()))
    assert (q["d"], q["f"], q["layers"], q["heads"], q["kv_heads"],
            q["head_dim"], q["vocab"], q["theta"], q["tied"],
            q["qkv_bias"]) == (896, 4864, 24, 14, 2, 64, 151936, 1e6,
                               True, True)
    d = W.dims(json.loads((ROOT / "chipbench/configs/deepseek-67b-l4.json")
                          .read_text()))
    assert (d["d"], d["f"], d["layers"], d["heads"], d["kv_heads"],
            d["head_dim"], d["vocab"], d["theta"], d["tied"],
            d["qkv_bias"]) == (8192, 22016, 4, 64, 8, 128, 102400, 1e4,
                               False, False)


def test_a_traffic_key_the_harness_does_not_read_is_refused(tmp_path):
    path = tmp_path / "poisson.json"
    path.write_text(json.dumps({"batch": 2, "prompt_len": 8, "gen": 4,
                                "check_requests": 2, "loop": "poisson"}))
    with pytest.raises(ValueError, match="loop"):
        catalog.traffic(path)


def test_program_config_takes_the_published_values():
    q = model.program_config(json.loads(
        (ROOT / "chipbench/configs/qwen2-0.5b.json").read_text()))
    assert q.attention.rope_theta == 1e6 and q.attention.qkv_bias
    assert (q.d_model, q.num_layers, q.attention.head_dim) == (896, 24, 64)
    d = model.program_config(json.loads(
        (ROOT / "chipbench/configs/deepseek-67b-l4.json").read_text()))
    assert (d.num_layers, d.d_model, d.attention.num_kv_heads,
            d.tie_embeddings) == (4, 8192, 8, False)
