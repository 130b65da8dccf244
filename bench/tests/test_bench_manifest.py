"""Every file BENCHMARK.json names loads, and the entries keep to the
benchmark's rules. CPU only."""
import importlib
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from fastbench import manifest, modes, readers, reference, work  # noqa: E402,F401

B = manifest.benchmark()
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]
NUMBERS = {"train": {"loss_gap", "grad_gap", "update_gap",
                     "window_compiles"},
           "fold": {"distogram_gap", "msa_logits_gap", "coords_gap",
                    "window_compiles"}}


def test_names_and_units():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in B["configs"]]
             + [w["traffic"] for w in B["workloads"]]
             + [k for c in B["configs"] for k in c["reduced"]])
    for n in names:
        assert manifest.NAME.match(n), n
    for m in METRICS:
        assert manifest.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = manifest.cell(cell)
    assert c.traffic["mode"] in NUMBERS
    importlib.import_module("fastbench.modes." + c.traffic["mode"])
    reference.Dims.from_config(c.config)
    assert set(c.check["limits"]) == NUMBERS[c.traffic["mode"]]
    assert c.check["limits"]["window_compiles"] == 0
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_every_metric_has_a_reader(cell):
    c = manifest.cell(cell)
    for name in c.end_to_end:
        assert c.metric_files[name]["quantity"] in ("setup_s", "per_unit_s")
    for name in c.per_layer:
        spec = c.metric_files[name]
        mod = importlib.import_module("fastbench.readers." + spec["reader"])
        assert callable(mod.read)
        params = spec.get("params", {})
        if "work" in params:
            assert params["work"] in work.KERNEL_WORK
        if "model_flops" in params:
            assert params["model_flops"] in work.MODEL_FLOPS


def test_moves_is_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e, m
        where = m.get("workloads", CELLS)
        for cell in where:
            assert manifest.metric_applies(e2e[m["moves"]], cell), (m, cell)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_config_files_are_under_paths_and_distinct():
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in B["paths"])
        with open(os.path.join(manifest.CHECKOUT, f)) as fh:
            json.load(fh)
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}


CELL_DATA = sorted((kind, f[:-len(".json")])
                   for kind in ("traffic", "workloads")
                   for f in os.listdir(os.path.join(BENCH, kind))
                   if f.endswith(".json"))


@pytest.mark.parametrize("kind,name", CELL_DATA,
                         ids=[f"{k}/{n}" for k, n in CELL_DATA])
def test_every_cell_data_file_is_named_by_a_cell(kind, name):
    # a traffic mix or a cell's limits that no cell names goes stale
    # against the configuration it was written for
    key = {"traffic": "traffic", "workloads": "name"}[kind]
    assert name in {w[key] for w in B["workloads"]}
