"""BENCHMARK.json as the harness reads it: every cell, configuration,
mix and metric found by its name in files of its own, the file's limits,
and references that import nothing of the program."""

from __future__ import annotations

import ast
import importlib
import json
import math
import pathlib
import re

import pytest
from conftest import REPO, tiny_overlay

from perfbench import check, harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"] + METRICS]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    assert {m["name"]: m["bound"] for m in BENCH["end_to_end"]}["setup_s"] == 0.25


def test_check_fits_its_time():
    """A full check of 24 cells at this run length fits its 43,200 s."""
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    c = harness.load_cell(REPO, cell, trace=False)
    assert set(c.limits) == set(check.NUMBERS)
    assert all(0 < v < 1 for v in c.limits.values())
    e2e = {m["name"] for m in c.metrics}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.load_cell(REPO, cell, trace=True)
    assert layer.metrics and {m["moves"] for m in layer.metrics} <= e2e


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_reader_found_by_name(metric):
    mod = harness._module(REPO / "perfbench" / "metrics" / f"{metric['name']}.py",
                          f"reader_{metric['name']}")
    assert callable(mod.read)
    assert mod.LAYER == metric.get("layer") and mod.MOVES == metric.get("moves")


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    cfg = json.loads((REPO / config["file"]).read_text())
    assert cfg["reduced"] == config["reduced"]
    importlib.import_module(f"perfbench.models.{cfg['model']}")
    importlib.import_module(f"perfbench.reference.{cfg['model']}")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_tiny_overlays(cell):
    """Every configuration and mix a cell uses has an overlay under
    ``perfbench/tiny/`` (``{}`` where it runs as it is on the CPU), so
    that the CPU tests never run a cell at its published size."""
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    config = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    for rel in (pathlib.Path(config["file"]).relative_to("perfbench"),
                pathlib.Path("traffic") / f"{w['traffic']}.json"):
        overlay = tiny_overlay(REPO, rel)
        assert overlay.is_file(), f"{cell}: no overlay {overlay.relative_to(REPO)}"
        assert isinstance(json.loads(overlay.read_text()), dict), overlay


@pytest.mark.parametrize(
    "path", sorted((REPO / "perfbench" / "reference").glob("*.py")),
    ids=lambda p: p.name)
def test_reference_imports_nothing_of_the_program(path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        assert not any(m == "repro" or m.startswith("repro.") for m in mods)


def test_peaks_keyed_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.peaks_for("TPU v9 imaginary")


def test_compare_by_worst_leaf():
    ref = check.readings([2.0, 1.0, 0.5], {"a": [3.0, 4.0], "b": [1e-9]},
                         {"a": [1.0], "b": [1e-12]})
    prog = check.readings([2.0, 1.001, 0.5], {"a": [3.0, 4.1], "b": [0.0]},
                          {"a": [1.5], "b": [1.0]})
    got = check.compare(prog, ref)
    assert got["loss"] == pytest.approx(1e-3)
    # |‖g‖ - ‖g_ref‖| over the larger of the leaf's norm and the median's
    assert got["grad"] == pytest.approx(abs(math.hypot(3, 4.1) - 5) / 5)
    # leaf b's reference gradient is under STILL of the median: left out
    assert got["change"] == pytest.approx(0.5)
    ok, checks = check.judge(got, {"loss": 1e-2, "grad": 1e-1, "change": 0.4})
    assert not ok and checks["change"]["limit"] == 0.4
    assert not check.judge({"loss": math.nan, "grad": 0, "change": 0},
                           {"loss": 1, "grad": 1, "change": 1})[0]
