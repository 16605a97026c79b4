"""Finds every piece of a cell by the names in ``BENCHMARK.json``:

- ``chipbench/configs/<config>.json`` (the configuration's ``file``),
- ``chipbench/traffic/<traffic>.json``,
- ``chipbench/limits/<workload>.json`` (the limit of each number that
  decides ``correct``),
- ``chipbench/metrics/<metric>.py``, exposing ``read(record)``.

A later cell, traffic mix, limit or metric is a new file; nothing here
changes.  A traffic mix is closed batches: ``batch`` rows, each a
prompt of ``prompt_len`` tokens and ``gen`` greedy tokens, all arriving
at the batch's start, the next batch once the last token of the one
before reached the host; ``check_requests`` of its finished requests
are compared with the reference.  A key the harness does not read is an
error, not a silent default.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass

ROOT = pathlib.Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _json(path: pathlib.Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


TRAFFIC_KEYS = {"batch", "prompt_len", "gen", "check_requests"}


def traffic(path: pathlib.Path) -> dict:
    t = _json(path)
    if set(t) != TRAFFIC_KEYS:
        raise ValueError(f"{path.name}: traffic keys {sorted(t)}; the "
                         f"harness reads exactly {sorted(TRAFFIC_KEYS)}")
    return t


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    base = root / "chipbench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(root / cfg["file"]),
        traffic=traffic(base / "traffic" / f"{w['traffic']}.json"),
        limits=_json(base / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader(metric: str, root: pathlib.Path = ROOT):
    """``read(record) -> float | None`` of ``metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
