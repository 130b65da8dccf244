"""The benchmark's data, found by name: ``BENCHMARK.json`` at the root of the
checkout, and under ``bench/`` one file per configuration
(``configs/<config>.json``), traffic mix (``traffic/<traffic>.json``), cell
(``workloads/<cell>.json``: the limits of its correctness check) and metric
(``metrics/<metric>.json``: the reader that computes it and the reader's
parameters). Adding a cell or a metric adds files and entries; no code
names one."""
from __future__ import annotations

import dataclasses
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    check: dict
    chips: int
    end_to_end: dict          # metric name -> BENCHMARK.json entry
    per_layer: dict           # metric name -> BENCHMARK.json entry
    metric_files: dict        # metric name -> its file under metrics/


def benchmark(checkout: str = CHECKOUT) -> dict:
    return _load(os.path.join(checkout, "BENCHMARK.json"))


def metric_applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(name: str, checkout: str = CHECKOUT) -> Cell:
    """Everything one cell needs, read from its files."""
    bench = benchmark(checkout)
    root = os.path.join(checkout, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if metric_applies(m, name)}
    layer = {m["name"]: m for m in bench["per_layer"]
             if metric_applies(m, name)}
    files = {m: _load(os.path.join(root, "metrics", m + ".json"))
             for m in list(e2e) + list(layer)}
    config = _load(os.path.join(root, "configs", w["config"] + ".json"))
    traffic = _load(os.path.join(root, "traffic", w["traffic"] + ".json"))
    if (traffic["n_seq"] != config["max_msa_clusters"]
            or traffic["n_res"] != config.get("crop_size", traffic["n_res"])):
        raise ValueError(f"{name}: traffic shapes differ from the "
                         f"configuration's crop_size / max_msa_clusters")
    return Cell(
        name=name, config=config, traffic=traffic,
        check=_load(os.path.join(root, "workloads", name + ".json")),
        chips=w["chips"], end_to_end=e2e, per_layer=layer,
        metric_files=files)
