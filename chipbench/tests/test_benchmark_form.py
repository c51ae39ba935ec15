"""BENCHMARK.json holds together: every name has its files, and every
per-layer metric is read only in cells that report what it moves."""
from __future__ import annotations

import json
import os
import re

import pytest

from chipbench.tests import helpers

with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _reported(cell: str, kind: str):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_its_files_and_metrics(cell):
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    confs = {c["name"]: c for c in BENCH["configs"]}
    assert os.path.isfile(os.path.join(helpers.REPO,
                                       confs[entry["config"]]["file"]))
    for sub, name in (("traffic", entry["traffic"]), ("cells", cell)):
        assert os.path.isfile(os.path.join(helpers.BENCH, sub,
                                           f"{name}.json")), (sub, name)
    e2e = _reported(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = _reported(cell, "per_layer")
    assert per_layer
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for name in per_layer:
        assert moves[name] in e2e, (name, moves[name])


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(kind):
    for m in BENCH[kind]:
        assert NAME.match(m["name"]), m["name"]
        assert os.path.isfile(os.path.join(helpers.BENCH, "metrics",
                                           f"{m['name']}.py")), m["name"]
        for cell in m.get("workloads", []):
            assert cell in CELLS, (m["name"], cell)
        if kind == "end_to_end":
            assert 0.01 <= m["bound"] <= 0.25, m["name"]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_names_its_modules(config):
    """The system under test, the plain reference and the work module
    that a configuration's file names are files of the benchmark."""
    entry = {c["name"]: c for c in BENCH["configs"]}[config]
    with open(os.path.join(helpers.REPO, entry["file"])) as f:
        conf = json.load(f)
    for key, sub in (("system", "systems"), ("reference", "configs"),
                     ("work", "configs")):
        assert conf.get(key), (config, key)
        assert os.path.isfile(os.path.join(helpers.BENCH, sub,
                                           f"{conf[key]}.py")), (config, key)
