"""Shared set-up of the benchmark's CPU tests: the benchmark's package
on the path, JAX as the program runs it (other test modules turn x64 on
as they are imported), and a copy of the benchmark at tiny sizes."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.append(p)

#: the cells' configurations, cut to sizes a CPU test holds.
TINY = {
    "configs/gcn-arxiv.json": {"nodes": 300, "edges": 2000, "features": 16,
                               "hidden": 32, "classes": 5},
    "configs/logreg-epsilon.json": {"rows": 4096, "features": 50},
    "traffic/minibatch.json": {"batch_rows": 512},
}


@pytest.fixture
def f32():
    saved = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", saved)


@pytest.fixture
def tiny_root(tmp_path, f32):
    """A copy of BENCHMARK.json and perfbench/ whose configurations are
    cut to the sizes in ``TINY``."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, sizes in TINY.items():
        path = tmp_path / "perfbench" / rel
        data = json.loads(path.read_text())
        data.update(sizes)
        path.write_text(json.dumps(data))
    return tmp_path


def run_cell(root, cell: str, *, seed: int = 2**31 + 7, seconds: float = 0.3,
             trace: bool = False):
    """One run of ``cell`` on the CPU: (exit code, result line or None,
    standard error)."""
    import io

    from perfbench import harness

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(cell, seed, seconds, trace, root=pathlib.Path(root),
                     check_device=False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
