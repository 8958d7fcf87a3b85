"""BENCHMARK.json resolves, by name alone, to the files of each cell, and
keeps to the shape later PRs build on."""

from __future__ import annotations

import json
import re

import pytest

from chipbench import cell as cell_lib
from chipbench.cell import HERE, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + WORKLOADS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    c = cell_lib.resolve(workload)
    assert c.config["serving"]["slots"] > 0
    assert c.mix["loop"] in ("open", "closed")
    assert "policy" in c.accum
    assert set(c.load["correct"]) == {"widest_gap", "tokens_checked"}
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    units = {m["name"]: m["unit"]
             for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for kind, names in (("end_to_end", c.end_to_end),
                        ("metrics", c.per_layer)):
        for name in names:
            mod = cell_lib.reader(kind, name)
            assert callable(mod.read) and mod.UNIT == units[name]


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", WORKLOADS):
            assert w in moved.get("workloads", WORKLOADS), (m["name"], w)


def test_config_files_list_every_change_from_the_source():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert sorted(cfg.get("published", {})) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_every_cell_file_is_a_workload():
    cells = {f.stem for f in (HERE / "cells").glob("*.json")}
    assert cells == set(WORKLOADS)
    mixes = {w["traffic"] for w in BENCH["workloads"]}
    assert mixes <= {f.stem for f in (HERE / "mixes").glob("*.json")}


def test_every_configuration_file_maps_onto_the_program():
    from chipbench import program

    for f in (HERE / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        got = program.model_config(cfg)
        assert got.num_layers == cfg["config"]["num_hidden_layers"]
