"""The harness end to end on the CPU: a cell and a metric added as new
files only, the result line, and the exits without a chip."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from conftest import CELLS, REPO, run_cell

from perfbench import harness

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_and_metric_are_new_files_only(tiny_root):
    before = _digests(tiny_root)
    base = tiny_root / "perfbench"
    (base / "configs" / "logreg-tiny.json").write_text(json.dumps({
        "model": "logreg", "source": "a throwaway", "rows": 1024,
        "features": 40, "step_size": 1.0, "reduced": [], "assumed": []}))
    (base / "traffic" / "quarter.json").write_text(json.dumps({"batch_rows": 256}))
    (base / "cells" / "logreg-tiny.quarter.json").write_text(json.dumps(
        {"limits": {"loss": 1e-3, "grad": 1e-2, "change": 1e-2}}))
    (base / "metrics" / "tiny.steps.py").write_text(
        'LAYER = "harness"\nMOVES = "step_ms"\n\n\ndef read(ctx):\n'
        '    return float(ctx.steps)\n')
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "logreg-tiny", "source": "a throwaway",
                             "file": "perfbench/configs/logreg-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "logreg-tiny.quarter", "config": "logreg-tiny",
                               "traffic": "quarter", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "step_ms":  # the cell's step time, under its bound
            m["workloads"].append("logreg-tiny.quarter")
    bench["per_layer"].append({"name": "tiny.steps", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "step_ms",
                               "workloads": ["logreg-tiny.quarter"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(tiny_root)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {tiny_root.joinpath("BENCHMARK.json").relative_to(tiny_root)}
    assert set(before) <= set(after)

    rc, res, err = run_cell(tiny_root, "logreg-tiny.quarter")
    assert rc == 0 and res["correct"], err
    assert set(res) == KEYS and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"step_ms", "setup_s"}  # no memory stats on CPU
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert err.strip().splitlines()[-1].startswith("check change:")

    rc, res, err = run_cell(tiny_root, "logreg-tiny.quarter", trace=True)
    assert rc == 0 and res["correct"], err
    assert res["metrics"]["tiny.steps"]["value"] == res["attempted"]
    assert res["metrics"]["engine.compiles"]["value"] == 0
    assert res["metrics"]["session.host_ms"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_cells_run_correct_at_tiny_size(tiny_root, cell):
    rc, res, err = run_cell(tiny_root, cell)
    assert rc == 0 and res["correct"] and res["failed"] == 0, err
    listed = {m["name"] for m in harness.load_cell(tiny_root, cell, False).metrics}
    assert set(res["metrics"]) == listed - {"peak_hbm_gib"}  # no memory stats on CPU
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "logreg-epsilon.full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_chip():
    proc = _command(REPO, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "accelerator" in proc.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0 and proc.stdout.strip() == ""


class _Chip:
    platform = "tpu"
    device_kind = "TPU v9 imaginary"


def test_exits_nonzero_on_a_device_kind_missing_from_the_peaks(
        tiny_root, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    import io

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("logreg-epsilon.full", 1, 1.0, False, root=tiny_root,
                     out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
    assert "TPU v9 imaginary" in err.getvalue()


def test_exits_nonzero_with_fewer_chips_than_the_cell(tiny_root, monkeypatch):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["chips"] = 4
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    chip = _Chip()
    chip.device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    import io

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(bench["workloads"][0]["name"], 1, 1.0, False,
                     root=tiny_root, out=out, err=err)
    assert rc != 0 and out.getvalue() == ""


class _Stats:
    def __init__(self, temp):
        self.temp_size_in_bytes = temp


class _Program:
    def __init__(self, temp):
        self._temp = temp

    def get_compiled_memory_stats(self):
        return _Stats(self._temp)


class _Memory:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_adds_the_window_programs_temporaries():
    """The runtime's peak counts live arrays; a step's temporaries, where
    a copy added inside the step lands, are added from its program."""
    chips = [_Memory({"peak_bytes_in_use": 100}), _Memory({"peak_bytes_in_use": 300})]
    programs = [_Program(50), _Program(2_000), _Program(0)]
    assert harness._memory_peak(chips, programs) == 300 + 2_000
    assert harness._memory_peak(chips, []) == 300
    assert harness._memory_peak([_Memory(None)], programs) is None
