"""Shared set-up of the benchmark's CPU tests: the benchmark's package
on the path, JAX as the program runs it (other test modules turn x64 on
as they are imported), a copy of the benchmark at tiny sizes, and the
checks that every cell has to pass there. Nothing here names a
configuration: the tiny sizes are the benchmark's own overlay files
(``perfbench/tiny/``), the faults are the models' own (``fault``), and a
cell on more than one chip runs in a process of its own on as many
virtual CPU devices."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.append(p)

import perfbench_lane  # noqa: E402

from perfbench import harness  # noqa: E402

LANE = pathlib.Path(perfbench_lane.__file__)
DATA = REPO / "perfbench" / "testdata"
#: the traces recorded on a chip, ``testdata/<name>.xplane.pb.gz``, each
#: with what it holds, ``testdata/<name>.json``: the cell it was recorded
#: from, whether the program's spans are in it, and what the readers of
#: the work-counting metrics read from it
RECORDED = {p.name[: -len(".json")]: json.loads(p.read_text())
            for p in sorted(DATA.glob("*.json"))}


@pytest.fixture
def f32():
    saved = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", saved)


def tiny_overlay(root, rel) -> pathlib.Path:
    """The overlay of ``perfbench/<rel>``: ``perfbench/tiny/<rel>``."""
    return pathlib.Path(root) / "perfbench" / "tiny" / rel


def apply_tiny(root) -> None:
    """Lay every overlay under ``perfbench/tiny/`` over the file of the
    same name under ``perfbench/``: its keys replace the file's. Laying
    them twice gives what laying them once does."""
    base = pathlib.Path(root) / "perfbench"
    for overlay in sorted((base / "tiny").rglob("*.json")):
        path = base / overlay.relative_to(base / "tiny")
        data = json.loads(path.read_text())
        data.update(json.loads(overlay.read_text()))
        path.write_text(json.dumps(data))


@pytest.fixture
def tiny_root(tmp_path, f32):
    """A copy of BENCHMARK.json and perfbench/ with every overlay of
    ``perfbench/tiny/`` laid over its configurations and mixes."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    apply_tiny(tmp_path)
    return tmp_path


def recorded_ctx(name: str) -> harness.Context:
    """What a metric reader reads from the recorded trace ``name``: the
    reduced trace, its steps (one ``wait`` span each) and the work its
    cell counts."""
    from perfbench import trace_reduce
    from perfbench.feed import Feed

    cell = harness.load_cell(REPO, RECORDED[name]["cell"], trace=True)
    red = trace_reduce.load(DATA / f"{name}.xplane.pb.gz")
    work = cell.model.work(cell.cfg, Feed(cell.traffic, cell.model.rows(cell.cfg), 0))
    return harness.Context(
        cell=cell, chips=cell.chips, work=work,
        steps=sum(1 for n, _, _ in red.spans if n == "wait"),
        window_s=red.window_s, setup_s=0.0, memory_peak_bytes=None, compiles=0,
        trace=red, peaks=harness.peaks_for("TPU v5 lite"))


def bench(root) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def chips(root, cell: str) -> int:
    return {w["name"]: int(w["chips"]) for w in bench(root)["workloads"]}[cell]


def in_lane(root, cell: str, what: str, **kwargs):
    """``perfbench_lane.<what>(root, cell, **kwargs)``: in this process
    for a one-chip cell; for a cell on more chips, in a process of its
    own with ``JAX_PLATFORMS=cpu`` and that many virtual CPU devices,
    which imports the benchmark from ``root``."""
    n = chips(root, cell)
    if n == 1:
        return getattr(perfbench_lane, what)(root, cell=cell, **kwargs)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(REPO / "src")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={n}").strip())
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, str(LANE), str(root), what,
         json.dumps(dict(kwargs, cell=cell))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_cell(root, cell: str, *, seed: int = 2**31 + 7, seconds: float = 0.3,
             trace: bool = False, fault=None):
    """One run of ``cell`` on the CPU, with the model's ``fault`` entered
    where one is named: (exit code, result line or None, standard
    error)."""
    return tuple(in_lane(root, cell, "run", seed=seed, seconds=seconds,
                         trace=trace, fault=fault))


def faults(root, cell: str) -> list:
    """The faults that the model of ``cell`` can be broken by."""
    return in_lane(root, cell, "faults")


# the checks that every cell passes at tiny size (tests/perfbench's
# parametrised tests run them on BENCHMARK.json's cells; the tests of a
# throwaway cell, on theirs)


def assert_control_fails(root, cell: str) -> None:
    """The control (the reference in bfloat16 in the program's place)
    fails the cell's limits on three seeds; the reference passes them
    against itself."""
    for ok, self_ok, checks in in_lane(root, cell, "control", seeds=[1, 2, 3]):
        assert not ok, checks
        assert self_ok


def assert_fault_fails(root, cell: str, fault: str) -> None:
    """A whole run with the timed path broken by ``fault`` comes out not
    correct, and says which number failed."""
    rc, res, err = run_cell(root, cell, seconds=0.1, fault=fault)
    assert rc == 0 and res["correct"] is False, err
    assert "FAIL" in err
    jax.clear_caches()


def assert_runs_correct(root, cell: str):
    """A run is correct, fails no step, and reports each end-to-end
    metric of the cell (the CPU has no memory statistics), each above
    0. Returns the result line and the standard error."""
    rc, res, err = run_cell(root, cell)
    assert rc == 0 and res["correct"] and res["failed"] == 0, err
    listed = {m["name"] for m in harness.metrics_for(bench(root), cell, False)}
    assert set(res["metrics"]) == listed - {"peak_hbm_gib"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    return res, err


def add_cell(root, *, config: dict, cfg: dict, tiny: dict, cell: dict,
             limits: dict) -> None:
    """Add a configuration and a cell of it to the benchmark at ``root``
    as a later PR would: new files (the configuration, its overlay, the
    cell's limits) and appended entries of BENCHMARK.json, the cell
    listed under ``step_ms``."""
    base = pathlib.Path(root) / "perfbench"
    (pathlib.Path(root) / config["file"]).write_text(json.dumps(cfg))
    tiny_overlay(root, pathlib.Path(config["file"]).relative_to("perfbench")
                 ).write_text(json.dumps(tiny))
    (base / "cells" / f"{cell['name']}.json").write_text(json.dumps({"limits": limits}))
    b = bench(root)
    b["configs"].append(config)
    b["workloads"].append(cell)
    for m in b["end_to_end"]:
        if m["name"] == "step_ms":  # the cell's step time, under its bound
            m["workloads"].append(cell["name"])
    (pathlib.Path(root) / "BENCHMARK.json").write_text(json.dumps(b))
