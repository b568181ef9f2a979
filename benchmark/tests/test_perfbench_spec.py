"""BENCHMARK.json keeps to the benchmark's contract, and every cell finds
its configuration, traffic, loop and metric files by name."""
from __future__ import annotations

import json
import re

import pytest

from tiny_cells import BENCH, ROOT, spec

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in spec()["workloads"]]


def test_top_level_keys():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["paths"] == ["benchmark"]
    assert s["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= s["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_bounds():
    s = spec()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in s[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in s["end_to_end"])


def test_each_cell_reports_what_it_must():
    s = spec()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    for cell in s["workloads"]:
        c = run.resolve(cell["name"])
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer, cell["name"]
        for m in c.per_layer:
            assert m["moves"] in names, (cell["name"], m["name"])
    for m in s["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        assert m["moves"] in e2e
    layers = {m["layer"] for m in s["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = run.resolve(cell)
    assert c.loop.is_file()
    assert c.cfg["name"] == c.cell["config"]
    for m in c.end_to_end + c.per_layer:
        mod = run.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_configs_list_what_they_change():
    for conf in spec()["configs"]:
        cfg = json.loads((ROOT / conf["file"]).read_text())
        assert cfg["reduced"] == conf["reduced"]
        assert cfg["source"] == conf["source"]
        for key in conf["reduced"]:
            assert cfg["published"][key] != cfg[key], key
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       and k != "vocab_size" for k in conf["reduced"])
