"""BENCHMARK.json against the rules the driver refuses a file over, as far
as they can be checked without the driver, and against the files it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_dim)$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"][1].startswith("benchmarks/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(bench):
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        for base in ("benchmarks", "benchmarks/tests/data"):
            assert os.path.exists(os.path.join(
                ROOT, base, "traffic", w["traffic"] + ".json")), w
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        # Reported only where the metric it moves is.
        mine = set(m.get("workloads", cells))
        assert mine <= set(e2e[m["moves"]].get("workloads", cells)), m
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reports = [m["name"] for m in bench["end_to_end"]
                   if cell in m.get("workloads", cells)]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def entries(key: str) -> list:
    """BENCHMARK.json's entries under ``key``, read when the tests are
    collected: a configuration or cell that a later PR appends is a case of
    the tests below without an edit here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)[key]


@pytest.mark.parametrize("entry", entries("configs"), ids=lambda c: c["name"])
def test_configuration_file_and_its_twin(entry):
    """What a configuration's file says of itself, held against its entry,
    its rehearsal twin and the files its job needs."""
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                           os.path.basename(entry["file"]))) as f:
        twin = json.load(f)
    assert config["name"] == entry["name"]
    assert config["job"] == twin["job"]
    for base in ("jobs", "reference"):
        assert os.path.exists(os.path.join(ROOT, "benchmarks", base,
                                           config["job"] + ".py"))
    # Each key cut from the published file stands there with the published
    # value beside it, and the two differ.
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert config["published"][key] != config[key], key
    if config["source"].startswith("https://"):
        assert config["source"] == entry["source"]
    # The twin carries the keys that were cut and the same optimizer: a rate
    # changed in one and not in the other would rehearse another program.
    # Why a rate is what it is, the file says itself (``assumed.optimizer``).
    assert {"job", "optimizer", "check"} | set(entry["reduced"]) <= set(twin)
    assert twin["optimizer"] == config["optimizer"]
