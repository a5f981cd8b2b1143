"""``BENCHMARK.json`` against the benchmark's contract: every file it
names exists, every name and unit is made of the allowed characters,
and every cell reports what it must."""
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source",
                   "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_characters(bench):
    assert set(bench) == KEYS["top"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert set(e) <= KEYS[group], (group, e["name"])
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 2 <= len(bench["command"]) <= 32
    assert all(one_line(w) for w in bench["command"])
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for w in bench["workloads"]:
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in bench["configs"]:
        assert one_line(c["why"]) and one_line(c["source"])
        assert len(c["reduced"]) <= 16
    for m in bench["per_layer"]:
        assert one_line(m["layer"])


def test_files_found_by_name(bench):
    paths = bench["paths"]

    def under_paths(rel):
        return any(rel.startswith(p.rstrip("/") + "/") for p in paths)

    assert under_paths(bench["command"][1])
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert under_paths(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["limits"], c["name"]
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) \
                as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(HERE, "drivers", f"{driver}.py"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py")), m["name"]
    assert {w["config"] for w in bench["workloads"]} == configs


def test_bounds_and_sources(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"] or \
                "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells(m):
        return set(m.get("workloads",
                         [w["name"] for w in bench["workloads"]]))

    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert cells(m) <= cells(e2e[m["moves"]]), m["name"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for w in bench["workloads"]:
        n = w["name"]
        assert sum(n in cells(m) for m in e2e.values()) >= 2, n
        assert any(n in cells(m) for m in bench["per_layer"]), n
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_check_fits_the_day(bench):
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
