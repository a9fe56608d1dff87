"""The harness end to end on the CPU: a configuration, a cell and a
metric added as new files only, on one chip and on four, the result
line, and the exits without a chip."""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest
from conftest import (CELLS, REPO, add_cell, apply_tiny, assert_control_fails,
                      assert_fault_fails, assert_runs_correct, faults, run_cell,
                      tiny_overlay)

from perfbench import harness

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _new_files_only(root, add) -> None:
    """``add(root)`` changes no file of the benchmark but BENCHMARK.json,
    and removes none."""
    before = _digests(root)
    add(root)
    after = _digests(root)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {pathlib.Path("BENCHMARK.json")}
    assert set(before) <= set(after)


def _add_logreg_tiny(root):
    base = root / "perfbench"
    (base / "traffic" / "quarter.json").write_text(json.dumps({"batch_rows": 4096}))
    tiny_overlay(root, "traffic/quarter.json").write_text(json.dumps({"batch_rows": 256}))
    (base / "metrics" / "tiny.steps.py").write_text(
        'LAYER = "harness"\nMOVES = "step_ms"\n\n\ndef read(ctx):\n'
        '    return float(ctx.steps)\n')
    add_cell(root,
             config={"name": "logreg-tiny", "source": "a throwaway",
                     "file": "perfbench/configs/logreg-tiny.json",
                     "reduced": [], "why": "test"},
             cfg={"model": "logreg", "source": "a throwaway", "rows": 65536,
                  "features": 40, "step_size": 1.0, "reduced": [], "assumed": []},
             tiny={"rows": 1024},
             cell={"name": "logreg-tiny.quarter", "config": "logreg-tiny",
                   "traffic": "quarter", "chips": 1, "why": "test"},
             limits={"loss": 1e-3, "grad": 1e-2, "change": 1e-2})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "tiny.steps", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "step_ms",
                               "workloads": ["logreg-tiny.quarter"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_and_metric_are_new_files_only(tiny_root):
    """A configuration of a model the benchmark has, with its overlay, a
    mix, a cell and a metric: new files and appended entries alone, and
    the cell passes what every cell passes, its model's faults too."""
    _new_files_only(tiny_root, _add_logreg_tiny)
    apply_tiny(tiny_root)
    cfg = json.loads((tiny_root / "perfbench/configs/logreg-tiny.json").read_text())
    assert cfg["rows"] == 1024

    res, err = assert_runs_correct(tiny_root, "logreg-tiny.quarter")
    assert set(res) == KEYS and list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"step_ms", "setup_s"}  # no memory stats on CPU
    assert res["attempted"] >= 1
    assert err.strip().splitlines()[-1].startswith("check change:")
    assert_control_fails(tiny_root, "logreg-tiny.quarter")
    for fault in faults(tiny_root, "logreg-tiny.quarter"):
        assert_fault_fails(tiny_root, "logreg-tiny.quarter", fault)

    rc, res, err = run_cell(tiny_root, "logreg-tiny.quarter", trace=True)
    assert rc == 0 and res["correct"], err
    assert res["metrics"]["tiny.steps"]["value"] == res["attempted"]
    assert res["metrics"]["engine.compiles"]["value"] == 0
    assert res["metrics"]["session.host_ms"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


FOUR = "rowshard-tiny.full"


def _add_rowshard(root):
    """A throwaway model on four chips, its reference, configuration,
    overlay and cell."""
    base = root / "perfbench"
    here = pathlib.Path(__file__).parent
    shutil.copy(here / "rowshard_model.py", base / "models" / "rowshard.py")
    shutil.copy(here / "rowshard_reference.py", base / "reference" / "rowshard.py")
    add_cell(root,
             config={"name": "rowshard-tiny", "source": "a throwaway",
                     "file": "perfbench/configs/rowshard-tiny.json",
                     "reduced": [], "why": "test"},
             cfg={"model": "rowshard", "source": "a throwaway", "rows": 262144,
                  "features": 64, "lr": 0.5, "reduced": [], "assumed": []},
             tiny={"rows": 1024},
             cell={"name": FOUR, "config": "rowshard-tiny", "traffic": "full",
                   "chips": 4, "why": "test"},
             limits={"loss": 1e-4, "grad": 1e-4, "change": 1e-4})


@pytest.fixture
def four_chip_root(tiny_root):
    _new_files_only(tiny_root, _add_rowshard)
    apply_tiny(tiny_root)
    return tiny_root


def test_four_chip_cell_runs_on_four_devices(four_chip_root):
    """A cell on four chips runs in a process of its own on four virtual
    CPU devices; the model is handed all four and shards its rows over
    them; the run is correct, and the control is not."""
    res, err = assert_runs_correct(four_chip_root, FOUR)
    assert res["device"]["count"] == 4
    assert "rowshard: 4 devices, x on 4" in err
    assert_control_fails(four_chip_root, FOUR)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "exchange"])
def test_four_chip_cell_faults_are_not_correct(four_chip_root, fault):
    assert fault in faults(four_chip_root, FOUR)
    assert_fault_fails(four_chip_root, FOUR, fault)


@pytest.mark.parametrize("cell", CELLS)
def test_cells_run_correct_at_tiny_size(tiny_root, cell):
    assert_runs_correct(tiny_root, cell)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
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
    rc = harness.run(CELLS[0], 1, 1.0, False, root=tiny_root,
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
