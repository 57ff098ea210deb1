"""BENCHMARK.json names only files that exist, and keeps its own rules."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for cell in manifest["workloads"]:
        conf = configs[cell["config"]]
        assert os.path.isfile(os.path.join(ROOT, conf["file"]))
        with open(os.path.join(ROOT, conf["file"])) as f:
            c = json.load(f)
        for part in (("runners", c["runner"] + ".py"),
                     ("reference", c["reference"] + ".py"),
                     ("traffic", cell["traffic"] + ".json"),
                     ("limits", cell["name"] + ".json")):
            assert os.path.isfile(os.path.join(BENCH, *part)), part
        assert cell["chips"] in (1, 4)


def test_every_metric_has_a_reader_and_its_cells(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell])


def test_names_and_units(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["name"] != "setup_s" or m["bound"] <= 0.25
        assert 0.01 <= m.get("bound", 0.01) <= 0.25
