"""BENCHMARK.json against the benchmark's contract, and every file a
cell is found by."""
import json
import re

import pytest

from chipbench import run as bench

ROOT = bench.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    raw = (ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    return json.loads(raw)


def line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == KEYS
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert manifest["paths"] == ["chipbench"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51


def test_names_units_and_lines(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["why"])
        assert line(c["source"]) and len(c["reduced"]) <= 16
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        names.append(w["name"])
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
            names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and line(m["layer"])
        assert set(m.get("workloads", ())) <= cells
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for w in manifest["workloads"]:
        mine = bench.cell_metrics(manifest, w, "end_to_end")
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert bench.cell_metrics(manifest, w, "per_layer")


def test_every_cell_resolves(manifest):
    used = set()
    for w in manifest["workloads"]:
        found = bench.resolve(ROOT, w["name"])
        used.add(w["config"])
        assert found["config"]["name"] == w["config"]
        traffic = found["traffic"]
        # the limits name numbers the check computes
        known = {"loss_gap", "loss_gap.call1", "kept_change_gap.call1",
                 "median_kept_change_gap.call1"} | {
            f"{kind}.call{i}" for i in traffic["checked_changes"]
            for kind in ("change_gap", "median_change_gap")}
        assert found["limits"] and set(found["limits"]) <= known
        assert all(0 < v < 1 for v in found["limits"].values())
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("chipbench/") for f in files)
