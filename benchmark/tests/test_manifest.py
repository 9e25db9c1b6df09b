"""BENCHMARK.json and every file it names, held to the rules that have
already cost a PR (PR 22 was refused for a layer named in plain words)."""
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert len(manifest["command"]) <= 32
    assert all(one_line(w) for w in manifest["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lengths(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), f"duplicate name in {group}"
        for n in names:
            assert NAME.match(n), f"{group} name {n!r}"
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1, m
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # PR 22: a layer is an identifier, not words
        assert NAME.match(m["layer"]), f"layer {m['layer']!r} of {m['name']}"
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_configs_and_cells(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        for folder, name in (("workloads", w["name"]),
                             ("traffic", w["traffic"])):
            path = os.path.join(ROOT, "benchmark", folder, name + ".json")
            assert os.path.isfile(path), path
            with open(path) as f:
                json.load(f)
    assert used == set(configs), "every configuration has at least one cell"
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    files = set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"] not in files
        files.add(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert "assumed" in body and "family" in body


def test_every_cell_reports_what_the_contract_asks(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def where(m):
        assert set(m.get("workloads", cells)) <= set(cells), m
        return set(m.get("workloads", cells))

    e2e = {m["name"]: where(m) for m in manifest["end_to_end"]}
    assert e2e.get("setup_s") == set(cells), "every cell reports setup_s"
    for cell in cells:
        assert sum(cell in s for s in e2e.values()) >= 2, cell
        assert any(cell in where(m) for m in manifest["per_layer"]), cell
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert where(m) <= e2e[m["moves"]], (
            f"{m['name']} moves {m['moves']}, which some of its cells "
            f"do not report")


def test_every_metric_and_kind_has_its_file(manifest):
    bench = os.path.join(ROOT, "benchmark")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in manifest["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(bench, "kinds", kind + ".py"))


def test_file_names_under_paths(manifest):
    for p in manifest["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in folder or ".pytest_cache" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel
