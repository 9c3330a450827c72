"""BENCHMARK.json holds to the benchmark's contract, and every item it
names has its file."""

import json
import os
import re

import pytest

import harness

SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_uniqueness():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    cells = {w["name"] for w in SPEC["workloads"]}
    for cell in cells:
        c = harness.load_cell(cell)
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert m["moves"] in e2e
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(cells) // 2)


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("metric", sorted(m["name"] for m in METRICS))
def test_each_metric_has_a_reader(metric):
    assert callable(harness._reader(metric))


def test_reader_family_and_missing_reader():
    assert harness._reader("idle_share.any_cell") is \
        harness._reader("idle_share")
    with pytest.raises(KeyError):
        harness._reader("no_such_metric.batch")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_has_its_files(cell):
    c = harness.load_cell(cell)
    assert callable(harness.plugin("loops", c.traffic["loop"]).Driver)
    assert callable(harness.plugin("entries", c.config["entries"]).Source)
    assert c.config["limits"]["max_rel_err"] > 0
    # the precision the limit was set for is stated, not left to a default
    assert c.config["solver"]["precision"] == "dq_acc"


def test_reduced_lists_match_config_files():
    for cfg in SPEC["configs"]:
        with open(os.path.join(harness.ROOT, cfg["file"])) as f:
            assert json.load(f)["reduced"] == cfg["reduced"]
