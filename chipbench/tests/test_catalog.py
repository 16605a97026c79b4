"""Every piece of every cell is found by its name, and BENCHMARK.json
keeps to the shape the harness and its checker read."""
import hashlib
import json
import pathlib
import re
import shutil
import time

import pytest

from chipbench import catalog
from chipbench.reference import dense_gqa

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
    assert cell.arch.__file__.endswith(f"reference/{c['reference']}.py")
    cell.arch.program_config(c)      # the program runs what the file says
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


def _hashes(root: pathlib.Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha256(f.read_bytes()).digest()
            for f in sorted(root.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts}


def test_a_new_architecture_is_new_files_alone(tmp_path):
    """A configuration of an architecture the harness has not seen joins
    by files of its own: its module ``reference/<name>.py`` (here a
    renamed re-export of ``dense_gqa``), its configuration, traffic and
    limits, and entries in BENCHMARK.json; a whole run of its cell then
    goes through that module, and no file the harness had is touched."""
    from chipbench import cell as C
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(root / "chipbench")
    assert before == _hashes(ROOT / "chipbench")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-twin", "source": "test",
                             "file": "chipbench/configs/tiny-twin.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-twin.decode", "config":
                               "tiny-twin", "traffic": "tiny", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = json.loads((ROOT / "chipbench/tests/data/tiny-qwen2.json")
                   .read_text())
    c.update(name="tiny-twin", reference="tiny_twin")
    new = {"configs/tiny-twin.json": json.dumps(c),
           "traffic/tiny.json": json.dumps({"batch": 4, "prompt_len": 32,
                                            "gen": 8, "check_requests": 4}),
           "limits/tiny-twin.decode.json": json.dumps({"token_gap": 0.25}),
           "reference/tiny_twin.py":
               "from chipbench.reference.dense_gqa import *  # noqa\n"}
    for name, text in new.items():
        (root / "chipbench" / name).write_text(text)
    cell = catalog.cell("tiny-twin.decode", root)
    assert cell.arch.__file__ == str(root / "chipbench" / "reference"
                                     / "tiny_twin.py")
    assert cell.arch.Reference is dense_gqa.Reference
    out = C.run_cell(cell, 2**35 + 7, 0.5, False, time.monotonic(),
                     require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    after = _hashes(root / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(new)


@pytest.mark.parametrize("reference,error", [
    (None, "no \"reference\" key"), ("../model", "no \"reference\" key"),
    ("no_such_arch", "names no module")])
def test_a_configuration_must_name_its_architecture(tmp_path, reference,
                                                     error):
    c = json.loads((ROOT / "chipbench/configs/qwen2-0.5b.json").read_text())
    c.pop("reference")
    if reference is not None:
        c["reference"] = reference
    path = tmp_path / "qwen2-0.5b.json"
    with pytest.raises((ValueError, FileNotFoundError)) as e:
        catalog.architecture(c, path)
    assert error in str(e.value) and str(path) in str(e.value)


def test_configuration_files_state_the_published_widths():
    q = dense_gqa.dims(json.loads((ROOT / "chipbench/configs/qwen2-0.5b.json")
                          .read_text()))
    assert (q["d"], q["f"], q["layers"], q["heads"], q["kv_heads"],
            q["head_dim"], q["vocab"], q["theta"], q["tied"],
            q["qkv_bias"]) == (896, 4864, 24, 14, 2, 64, 151936, 1e6,
                               True, True)
    d = dense_gqa.dims(json.loads(
        (ROOT / "chipbench/configs/deepseek-67b-l4.json").read_text()))
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
    q = dense_gqa.program_config(json.loads(
        (ROOT / "chipbench/configs/qwen2-0.5b.json").read_text()))
    assert q.attention.rope_theta == 1e6 and q.attention.qkv_bias
    assert (q.d_model, q.num_layers, q.attention.head_dim) == (896, 24, 64)
    d = dense_gqa.program_config(json.loads(
        (ROOT / "chipbench/configs/deepseek-67b-l4.json").read_text()))
    assert (d.num_layers, d.d_model, d.attention.num_kv_heads,
            d.tie_embeddings) == (4, 8192, 8, False)
